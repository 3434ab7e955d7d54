"""Golden-token regression: every execution variant vs ONE pinned output.

Each family has a fixture under ``tests/golden/`` holding a tiny
deterministic case — init seed, prompts, side-input seed — plus the
greedy outputs it produced when the fixture was generated (CPU,
float32). Every execution variant of the same math must reproduce
those tokens EXACTLY:

- the scan-over-layers serving path (the default),
- the unrolled ``scan_layers=False`` oracle (Python loop over the same
  stacked params),
- the mesh-sharded engine (data axis; expert axis for MoE),
- the psq-packed engine with the ternary sparsity skip on AND off
  (pinned separately as ``outputs_psq`` — packed weights are a
  different model than fp32).

A variant comparing equal to the golden is a much stronger statement
than pairwise A==B checks: a regression in the SHARED path (e.g. the
block math itself) moves every variant together and pairwise parity
would still pass. See docs/testing.md.

Regenerate after an intentional numerics change:

    PYTHONPATH=src python tests/test_golden_parity.py --regen

and commit the diff — the review question becomes "should these tokens
have changed?".
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.configs import get_config
from repro.core.config import PSQ_TERNARY
from repro.launch.mesh import make_mesh
from repro.models import init_model
from repro.serve import (
    EngineConfig, PackedModelCache, ServeEngine, pack_tree_psq,
)

GOLDEN_DIR = Path(__file__).parent / "golden"

# one arch per family; psq-packed goldens for the families the packed
# serving suites run end to end (dense + moe covers both FFN shapes)
ARCHS = ("tinyllama-1.1b", "granite-moe-3b-a800m", "zamba2-7b",
         "xlstm-350m", "whisper-large-v3", "llava-next-mistral-7b")
PSQ_ARCHS = ("tinyllama-1.1b", "granite-moe-3b-a800m")
# side-input families: continuous admission scatters per-slot
# enc-cross-KV / patch pools — pinned against the same golden as the
# static oracle and one-at-a-time decoding
SIDE_ARCHS = ("whisper-large-v3", "llava-next-mistral-7b")
# pure KV-cache families: speculative decoding must reproduce the
# vanilla golden token for token at every spec_k
SPEC_ARCHS = ("tinyllama-1.1b", "granite-moe-3b-a800m",
              "whisper-large-v3", "llava-next-mistral-7b")

MAX_LEN = 48
MAX_NEW = 6
N_REQ = 3


def _load(arch):
    path = GOLDEN_DIR / f"{arch}.json"
    with open(path) as f:
        return json.load(f)


def _case_prompts(case):
    return [np.asarray(p, dtype=np.int32) for p in case["prompts"]]


def _extra_inputs(cfg, case):
    """Side inputs regenerated from the pinned seed (not stored raw —
    a float tensor in JSON would dwarf the tokens it pins)."""
    rng = np.random.RandomState(case["extra_seed"])
    if cfg.family == "encdec":
        return {"enc_embeds": (rng.randn(N_REQ, 8, cfg.d_model)
                               * 0.1).astype(np.float32)}
    if cfg.family == "vlm":
        return {"patch_embeds": (rng.randn(N_REQ, cfg.frontend_len,
                                           cfg.d_model)
                                 * 0.1).astype(np.float32)}
    return {}


def _serve(cfg, params, case, mesh=None, mode="auto", max_batch=N_REQ,
           spec_k=0, draft=None):
    dcfg, dparams = draft if draft is not None else (None, None)
    eng = ServeEngine(params, cfg,
                      EngineConfig(max_batch=max_batch, max_len=MAX_LEN,
                                   mode=mode, spec_k=spec_k,
                                   draft_config=dcfg),
                      extra_inputs=_extra_inputs(cfg, case), mesh=mesh,
                      draft_params=dparams)
    for i, p in enumerate(_case_prompts(case)):
        eng.submit(p, max_new_tokens=MAX_NEW, extra_idx=i)
    done = {r.uid: r.output for r in eng.run()}
    return [done[uid] for uid in sorted(done)]


def _draft_model(cfg):
    """Tiny same-family draft: a 1-layer copy of the served config.

    Randomly initialized, so its proposals rarely match — which makes
    the golden check strict: acceptance, rejection and rollback all
    exercise on every trace, and the output STILL must be the vanilla
    golden."""
    dcfg = dataclasses.replace(cfg, n_layers=1)
    return dcfg, init_model(jax.random.PRNGKey(1), dcfg)


def _fp_model(arch):
    cfg = get_config(arch).reduced()
    return cfg, init_model(jax.random.PRNGKey(0), cfg)


def _psq_model(arch, sparsity_skip=True):
    cfg = get_config(arch).reduced()
    qcfg = dataclasses.replace(PSQ_TERNARY, kernel_backend="reference",
                               xbar_rows=64, sparsity_skip=sparsity_skip)
    cfg = cfg.with_quant(qcfg)
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, pack_tree_psq(params, qcfg, PackedModelCache())


class TestGoldenParity:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_scan_path_matches_golden(self, arch):
        case = _load(arch)
        cfg, params = _fp_model(arch)
        assert _serve(cfg, params, case) == case["outputs"], \
            f"{arch}: scan-path greedy outputs drifted from the golden"

    @pytest.mark.parametrize("arch", ARCHS)
    def test_unrolled_loop_matches_golden(self, arch):
        """scan_layers=False: same stacked params, Python loop instead
        of lax.scan — bit-exact under jit, so the SAME golden."""
        case = _load(arch)
        cfg, params = _fp_model(arch)
        cfg = dataclasses.replace(cfg, scan_layers=False)
        assert _serve(cfg, params, case) == case["outputs"], \
            f"{arch}: unrolled layer loop diverged from the golden"

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    @pytest.mark.parametrize("arch", ARCHS)
    def test_data_sharded_matches_golden(self, arch):
        case = _load(arch)
        cfg, params = _fp_model(arch)
        mesh = make_mesh((2, 1), ("data", "model"))
        assert _serve(cfg, params, case, mesh=mesh) == case["outputs"], \
            f"{arch}: data-sharded engine diverged from the golden"

    @pytest.mark.skipif(len(jax.devices()) < 4, reason="needs >= 4 devices")
    def test_moe_expert_parallel_matches_golden(self):
        arch = "granite-moe-3b-a800m"
        case = _load(arch)
        cfg, params = _fp_model(arch)
        mesh = make_mesh((1, 1, 4), ("data", "model", "expert"))
        assert _serve(cfg, params, case, mesh=mesh) == case["outputs"], \
            "expert-parallel MoE serving diverged from the golden"

    @pytest.mark.parametrize("arch", SIDE_ARCHS)
    @pytest.mark.parametrize("mode,mb", [("continuous", N_REQ),
                                         ("static", N_REQ),
                                         ("continuous", 1)],
                             ids=("continuous", "static", "sequential"))
    def test_side_input_modes_match_golden(self, arch, mode, mb):
        """encdec/VLM-with-patches on the continuous slot pool, the
        static oracle loop and one-at-a-time decoding all reproduce the
        same golden: per-slot side-input pools are bit-exact."""
        case = _load(arch)
        cfg, params = _fp_model(arch)
        assert _serve(cfg, params, case, mode=mode,
                      max_batch=mb) == case["outputs"], \
            f"{arch}: {mode} (batch {mb}) diverged from the golden"

    @pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
    @pytest.mark.parametrize("arch", SIDE_ARCHS)
    def test_side_input_continuous_2way_data_mesh(self, arch):
        """The per-slot side-input pools shard over ``data`` like every
        other cache leaf: 2-way data-parallel continuous serving stays
        on the golden."""
        case = _load(arch)
        cfg, params = _fp_model(arch)
        mesh = make_mesh((2, 1), ("data", "model"))
        assert _serve(cfg, params, case, mesh=mesh,
                      mode="continuous") == case["outputs"], \
            f"{arch}: 2-way data-sharded continuous diverged"

    @pytest.mark.parametrize("arch", SPEC_ARCHS)
    @pytest.mark.parametrize("spec_k", (2, 4))
    def test_spec_decode_matches_golden(self, arch, spec_k):
        """Speculative decoding is token-identical to vanilla greedy by
        construction — every emitted token is a main-model argmax at the
        same cache state — so the ONE golden pins it at every spec_k."""
        case = _load(arch)
        cfg, params = _fp_model(arch)
        assert _serve(cfg, params, case, spec_k=spec_k,
                      draft=_draft_model(cfg)) == case["outputs"], \
            f"{arch}: spec decode (k={spec_k}) diverged from the golden"

    @pytest.mark.parametrize("arch", PSQ_ARCHS)
    @pytest.mark.parametrize("skip", (True, False))
    def test_psq_sparsity_skip_matches_golden(self, arch, skip):
        """The ternary sparsity skip is an execution shortcut, not a
        numerics change: skip on and off both reproduce outputs_psq."""
        case = _load(arch)
        cfg, params = _psq_model(arch, sparsity_skip=skip)
        assert _serve(cfg, params, case) == case["outputs_psq"], \
            f"{arch}: psq serving (sparsity_skip={skip}) drifted"


def main():
    GOLDEN_DIR.mkdir(exist_ok=True)
    for arch in ARCHS:
        cfg = get_config(arch).reduced()
        rng = np.random.RandomState(11)
        case = {
            "arch": arch,
            "family": cfg.family,
            "init_seed": 0,
            "extra_seed": 7,
            "max_new_tokens": MAX_NEW,
            "prompts": [
                rng.randint(0, cfg.vocab_size,
                            size=int(rng.randint(4, 13))).tolist()
                for _ in range(N_REQ)
            ],
        }
        cfg, params = _fp_model(arch)
        case["outputs"] = _serve(cfg, params, case)
        if arch in PSQ_ARCHS:
            qcfg, qparams = _psq_model(arch)
            case["outputs_psq"] = _serve(qcfg, qparams, case)
        path = GOLDEN_DIR / f"{arch}.json"
        with open(path, "w") as f:
            json.dump(case, f, indent=1)
            f.write("\n")
        print(f"[golden] wrote {path}")


if __name__ == "__main__":
    import sys

    if "--regen" not in sys.argv:
        sys.exit("usage: PYTHONPATH=src python tests/test_golden_parity.py "
                 "--regen")
    main()
