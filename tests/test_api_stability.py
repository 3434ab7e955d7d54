"""Public-API stability: imports, stats() keys, pinned jit closures.

The engine decomposition (scheduler / state / executor behind the
``ServeEngine`` facade) must not move or rename anything callers use:
every public import path resolves, ``stats()`` keeps its key set, and
the jit closures the compile-count suite introspects keep their names
and their per-instance ``_cache_size`` hook."""
import importlib

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import init_model
from repro.serve import EngineConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

# every name importable from repro.serve before the decomposition,
# plus the scheduler/state layer names the decomposition added
PUBLIC_API = {
    "repro.serve": [
        "PackedLayer", "PackedModelCache", "pack_tree_psq",
        "ServeEngine", "throughput_stats",
        "BlockPool", "PagedKVManager", "PoolExhausted",
        "RadixPrefixIndex",
        "ADMISSION_POLICIES", "AdmissionPolicy", "CostAwareEnergyBudget",
        "EnergyModel", "EngineConfig", "Pow2BucketFCFS", "Request",
        "resolve_admission_policy",
        "ContiguousSlotState", "PagedSlotState", "SlotState",
    ],
    "repro.serve.engine": ["ServeEngine", "throughput_stats"],
    "repro.serve.scheduler": ["EngineConfig", "Request", "next_pow2"],
    "repro.serve.cache": ["PackedLayer", "pack_tree_psq"],
    "repro.serve.paged_kv": ["PagedKVManager", "PoolExhausted"],
    "repro.launch.serve": ["StreamingFrontend"],
}

# the stats() key set before the decomposition — supersets are fine,
# removals/renames are not
STATS_KEYS = {
    "mode", "decode_steps", "host_syncs", "decode_wall_s", "mean_step_s",
    "prefill_calls", "prefill_tokens", "cached_prefix_tokens",
    "mean_slot_occupancy", "admissions", "mesh",
    "energy_style", "energy_tokens", "energy_pj_per_token",
    "energy_pj_total", "energy_pj_per_request", "edap_total",
    "mean_occupancy",
}

# jit closures tests/benchmarks introspect by name (compile counts)
PINNED_CLOSURES = ["_prefill_full", "_prefill_bucket", "_decode",
                   "_insert", "_decode_multi"]
PINNED_PAGED = ["_decode_paged", "_insert_paged", "_prefill_suffix",
                "_copy_page", "_decode_multi_paged"]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tinyllama-1.1b").reduced()
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


def test_public_imports_resolve():
    for module, names in PUBLIC_API.items():
        mod = importlib.import_module(module)
        for name in names:
            assert hasattr(mod, name), f"{module}.{name} is gone"


def test_engine_config_defaults_are_compatible():
    """New knobs must default to the old behavior."""
    ecfg = EngineConfig()
    assert ecfg.admission_policy == "fcfs"
    assert ecfg.energy_budget_pj == 0.0
    assert ecfg.mode == "auto"


def test_stats_keys_and_pinned_closures(tiny):
    cfg, params = tiny
    eng = ServeEngine(params, cfg, EngineConfig(max_batch=2, max_len=64))
    eng.submit(np.arange(4), max_new_tokens=2)
    eng.run()
    s = eng.stats()
    missing = STATS_KEYS - set(s)
    assert not missing, f"stats() lost keys: {sorted(missing)}"
    assert s["admission_policy"] == "fcfs"
    for name in PINNED_CLOSURES:
        fn = getattr(eng, name)
        assert callable(fn), name
        assert fn._cache_size() >= 0


def test_paged_engine_pinned_closures(tiny):
    cfg, params = tiny
    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=2, max_len=64, paged=True,
                                   block_size=16))
    for name in PINNED_CLOSURES + PINNED_PAGED:
        assert callable(getattr(eng, name)), name
    assert "paged" in eng.stats()


def test_engine_attributes_survive(tiny):
    """Non-closure attributes external code reads off the engine."""
    cfg, params = tiny
    eng = ServeEngine(params, cfg, EngineConfig(max_batch=2, max_len=64))
    for attr in ("mode", "queue", "finished", "energy", "policy",
                 "state", "admitter", "executor", "energy_tokens",
                 "drained", "mesh"):
        assert hasattr(eng, attr), attr
    assert eng.mode == "continuous"
    assert eng.drained
