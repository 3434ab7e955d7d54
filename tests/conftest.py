"""Tier-1 suite environment: 4 virtual CPU devices + shared fixtures.

The sharded-serving tests (tests/test_sharding.py,
tests/test_serve_engine.py) need a multi-device mesh. On CPU, JAX forges
virtual devices via ``--xla_force_host_platform_device_count``, which is
only honored if set before the XLA backend initializes — hence this
conftest, which pytest imports before any test module. An explicit
``XLA_FLAGS`` in the environment (e.g. the CI ``mesh4`` job, or a
deliberate single-device run) wins; the multi-device tests skip
themselves when fewer devices exist than they need.
"""
import os

_FLAG = "--xla_force_host_platform_device_count"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" {_FLAG}=4"
    )

import pytest  # noqa: E402  (XLA_FLAGS must be set first)


@pytest.fixture
def compile_counts():
    """Shared jit compile counter for the no-recompile suites.

    Returns ``counts(*fns) -> List[int]``: the per-function jit cache
    sizes, read through the private ``_cache_size`` introspection hook.

    The canonical pins (see docs/testing.md):

    - one compile per (family, phase): a single-bucket trace leaves
      every engine phase closure (prefill / insert / decode) at cache
      size 1 — the scan-over-layers forwards trace the block once per
      phase, never per layer;
    - warm == rerun: repeating an already-served workload adds zero
      compilations.
    """
    def counts(*fns):
        return [f._cache_size() for f in fns]
    return counts
