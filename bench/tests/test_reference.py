"""The plain reference computes what the program's own oracles compute.

The reference imports nothing of the program; this test does, to show
at a small size that both implement the same PSQ layer and model.
"""
import jax
import numpy as np
import pytest

from bench.models import dense_psq as M
from bench.tests.tiny import tiny_config


@pytest.fixture(scope="module")
def drawn():
    cfg = tiny_config()
    return cfg, M.draw(cfg, 2147483813)


def test_drawn_weights_are_served_codes(drawn):
    cfg, w = drawn
    codes = np.asarray(w["gate"]["codes"])
    assert codes.dtype == np.int8 and codes.min() >= -8 and codes.max() <= 7
    sf = np.asarray(w["gate"]["sf_q"]) / (np.sqrt(128) / 8)
    assert np.allclose(sf, np.round(sf))
    assert np.round(sf).min() >= 4 and np.round(sf).max() <= 12
    again = M.draw(cfg, 2147483813)
    assert np.array_equal(codes, np.asarray(again["gate"]["codes"]))


@pytest.mark.parametrize("name", ["wq", "wk", "down"])
def test_psq_layer_matches_the_program_oracle(drawn, name):
    cfg, w = drawn
    _, params = M.program(cfg, w, backend="reference")
    group = "mlp" if name in ("gate", "up", "down") else "attn"
    layer = jax.tree.map(lambda a: a[0], params["blocks"][group][name])
    one = jax.tree.map(lambda a: a[0], w[name])
    x = jax.random.normal(jax.random.PRNGKey(1), (300, one["codes"].shape[0]))
    got = M.psq_linear(x, one, cfg, "highest")
    want, _ = layer.apply_serving(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_forward_matches_the_program(drawn):
    from repro.models import forward

    cfg, w = drawn
    arch, params = M.program(cfg, w, backend="reference")
    toks = jax.random.randint(jax.random.PRNGKey(2), (1, 96), 0,
                              cfg["vocab_size"])
    with jax.default_matmul_precision("highest"):
        want = forward(params, arch, {"tokens": toks})[0][0]
    got = M.forward(cfg, w, toks[0], "highest")
    assert np.mean(np.argmax(got, -1) == np.argmax(want, -1)) >= 0.98
    np.testing.assert_allclose(np.median(np.abs(got - want)), 0, atol=1e-4)
