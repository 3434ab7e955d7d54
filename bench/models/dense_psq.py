"""Dense decoder on the HCiM datapath: weights, the program's view of
them, and the plain reference.

Weights are drawn on the device from the seed in the form they are
served in: int8 LSQ weight codes, fixed-point scale factors (integer
codes times one step per layer), the LSQ steps and the comparator
threshold at the values of the repo's LSQ initialisation, float32
embedding and LM head. The program is handed the same arrays in its own
types (``program``). The reference (``forward``) reads them as data and
implements the model from its equations: no code of the program is
imported by it.

Every PSQ projection computes, per input bit-stream j and weight
bit-slice k of two's-complement codes, the crossbar partial sums of
each tile of R rows, the comparator input a = 2*ps - rowsum, a ternary
decision at +-alpha, the scale-factor accumulate, and the
unipolar-to-bipolar offset (HCiM, arXiv:2403.13577, section 4):

    y = s_x * s_w * (0.5 * sum_jk sigma_j kappa_k sum_t p_jkt * sf_tjk
                     + 0.5 * c_w * sum_i x_int_i)

``mode`` sets the precision of the reference's float matrix products
(attention and the LM head): ``"highest"`` or ``"high"`` (XLA's
``Precision``; ``"high"`` is three bf16 passes, the configurations'
stated ``matmul_precision``), or ``"bf16"``, one bf16 pass with float32
accumulation, the precision below it: the control of the correctness
check. The bit-plane products are exact integers in every mode, and the
scale-factor accumulate, float32 arithmetic in the HCiM datapath and in
the program's kernel, is exact in every mode but the control's.

The LM head's columns are drawn with zero mean over the hidden size.
At these drawn weights every PSQ projection adds to its output one
value common to all its columns (the comparator's bias on the unequal
bit planes of bell-shaped codes, and attention averaging it), so after
four layers the hidden state lies 98-99% along the all-ones direction,
the same for every token. A head with non-zero column sums turns that
direction into one argmax at every position: the served tokens would be
one or two tokens, and no loss of precision could move them.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

ROW_BLOCK = 256      # rows per block of the reference's PSQ products
NORM_EPS = 1e-6      # the program's RMSNorm epsilon (see the config files)


def psq_layers(cfg: Dict) -> List[Tuple[str, int, int]]:
    """(name, K, O) of every PSQ projection of one decoder layer."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hk, ff = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["intermediate_size"])
    return [("wq", d, h * hd), ("wk", d, hk * hd), ("wv", d, hk * hd),
            ("wo", h * hd, d), ("gate", d, ff), ("up", d, ff),
            ("down", ff, d)]


def _key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 62 bits, folded in 31-bit words."""
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _dims(cfg: Dict) -> tuple:
    p = cfg["psq"]
    return (cfg["num_hidden_layers"], cfg["hidden_size"],
            cfg["vocab_size"], tuple(psq_layers(cfg)), p["n_bits_a"],
            p["n_bits_w"], p["n_bits_sf"], p["xbar_rows"])


def _centered(head):
    """Columns with zero sum: the head reads nothing of the direction
    common to every hidden state (module docstring)."""
    return head - jnp.mean(head, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnums=0)
def _draw(dims: tuple, key: jax.Array) -> Dict:
    n_layers, d, vocab, layers, n_a, n_w, n_sf, r = dims
    keys = iter(jax.random.split(key, 3 + 2 * len(layers)))
    a_qp, w_qp = 2 ** (n_a - 1) - 1, 2 ** (n_w - 1) - 1
    w = {
        "embed": 0.02 * jax.random.normal(next(keys), (vocab, d)),
        "head": _centered(jax.random.normal(next(keys), (d, vocab))
                          / math.sqrt(d)),
        "final_norm": jnp.ones((d,), jnp.float32),
        "norm1": jnp.ones((n_layers, d), jnp.float32),
        "norm2": jnp.ones((n_layers, d), jnp.float32),
    }
    full = lambda v: jnp.full((n_layers,), v, jnp.float32)
    for name, k, o in layers:
        t = -(-k // r)
        # LSQ codes of N(0, 1/K) weights at the LSQ initial step
        # 2 / (sqrt(K) sqrt(qp)): round(N(0, sqrt(qp) / 2)), clipped
        z = jax.random.normal(next(keys), (n_layers, k, o))
        codes = jnp.clip(jnp.round(z * (math.sqrt(w_qp) / 2)),
                         -(w_qp + 1), w_qp).astype(jnp.int8)
        # fixed-point scale factors: integer codes around the initial
        # value sqrt(R) (code 8) times the layer step sqrt(R) / 2^(n_sf-1)
        sf_codes = jax.random.randint(next(keys), (n_layers, t, n_a, n_w, o),
                                      4, 13)
        sf_step = math.sqrt(r) / 2 ** (n_sf - 1)
        w[name] = {
            "codes": codes,
            "sf_q": sf_codes.astype(jnp.float32) * sf_step,
            "s_w": full(2.0 / (math.sqrt(k) * math.sqrt(w_qp))),
            "step_x": full(2.0 / math.sqrt(a_qp)),
            "alpha": full(0.67 * math.sqrt(r / 2.0)),
        }
    return w


def draw(cfg: Dict, seed: int) -> Dict:
    """Every weight of the model, on the device, in one jitted call."""
    return _draw(_dims(cfg), _key(seed))


def program(cfg: Dict, w: Dict, backend=None):
    """(ArchConfig, params) of the program for the drawn weights.

    Each projection is a scan-stacked ``PackedLayer`` holding the drawn
    arrays. Its int4 planes are left out (no serving path reads them),
    and so is its occupancy metadata: random codes leave no crossbar
    tile's column block all zero, so the kernel would take its dense
    path either way.
    """
    from repro.configs.base import ArchConfig
    from repro.core.config import QuantConfig
    from repro.core.quant import QuantSpec
    from repro.serve.cache import PackedLayer

    p = cfg["psq"]
    qcfg = QuantConfig(
        mode="psq", psq_levels=p["levels"], xbar_rows=p["xbar_rows"],
        spec=QuantSpec(n_bits_a=p["n_bits_a"], n_bits_w=p["n_bits_w"],
                       n_bits_sf=p["n_bits_sf"]),
        kernel_backend=backend)
    arch = ArchConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        sliding_window=cfg["sliding_window"] or 0, quant=qcfg)
    n = cfg["num_hidden_layers"]

    def bits(b):
        v = [2.0 ** i for i in range(b)]
        v[-1] = -v[-1]
        return jnp.broadcast_to(jnp.asarray(v, jnp.float32), (n, b))

    def packed(x):
        return PackedLayer(cfg=qcfg, w_codes=x["codes"], s_w=x["s_w"],
                           sf_q=x["sf_q"], alpha=x["alpha"],
                           step_x=x["step_x"], sigma=bits(p["n_bits_a"]),
                           kappa=bits(p["n_bits_w"]))

    params = {
        "embed": {"table": w["embed"]},
        "final_norm": {"scale": w["final_norm"]},
        "lm_head": {"w": w["head"]},
        "blocks": {
            "norm1": {"scale": w["norm1"]},
            "norm2": {"scale": w["norm2"]},
            "attn": {k: packed(w[k]) for k in ("wq", "wk", "wv", "wo")},
            "mlp": {k: packed(w[k]) for k in ("gate", "up", "down")},
        },
    }
    return arch, params


# -- the plain reference ------------------------------------------------------

def _bf16(x):
    """``x`` rounded to bf16, held in float32."""
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def exact_dot(spec: str, a, b):
    """A product of bf16-representable float32 operands: one bf16 pass
    with float32 accumulation is exact (on the CPU, float32 is)."""
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


def dot(spec: str, a, b, mode: str):
    """A float matrix product at the reference's precision."""
    if mode in ("highest", "high"):
        return jnp.einsum(spec, a, b,
                          precision=jax.lax.Precision[mode.upper()])
    if mode != "bf16":
        raise ValueError(f"unknown precision mode {mode!r}")
    return exact_dot(spec, _bf16(a), _bf16(b))


def psq_linear(x, p: Dict, cfg: Dict, mode: str):
    """One PSQ projection of rows ``x`` (N, K), in blocks of rows."""
    q = cfg["psq"]
    n_a, n_w, r = q["n_bits_a"], q["n_bits_w"], q["xbar_rows"]
    n, k = x.shape
    o = p["codes"].shape[1]
    t = -(-k // r)
    s_x = jnp.maximum(p["step_x"], 1e-9)
    x_int = jnp.round(jnp.clip(x / s_x, -(2 ** (n_a - 1)), 2 ** (n_a - 1) - 1))
    u_w = jnp.pad(p["codes"].astype(jnp.int32) & (2 ** n_w - 1),
                  ((0, t * r - k), (0, 0))).reshape(t, r, o)
    w_bits = jnp.stack([((u_w >> b) & 1).astype(jnp.float32)
                        for b in range(n_w)])                  # (n_w,T,R,O)
    sigma = [2.0 ** j for j in range(n_a)]
    sigma[-1] = -sigma[-1]
    kappa = [2.0 ** b for b in range(n_w)]
    kappa[-1] = -kappa[-1]
    sig, kap = jnp.asarray(sigma), jnp.asarray(kappa)
    alpha = jnp.maximum(p["alpha"], 1e-6)
    sf_mode = "bf16" if mode == "bf16" else "highest"
    nb = -(-n // ROW_BLOCK)
    xp = jnp.pad(x_int, ((0, nb * ROW_BLOCK - n), (0, t * r - k)))

    def block(xb):                                             # (RB, T*R)
        u = xb.astype(jnp.int32) & (2 ** n_a - 1)
        x_bits = jnp.stack([((u >> j) & 1).astype(jnp.float32)
                            for j in range(n_a)]).reshape(
                                n_a, ROW_BLOCK, t, r)
        rowsum = jnp.sum(x_bits, axis=-1)                      # (n_a,RB,T)

        def pair(i, acc):
            j, b = i // n_w, i % n_w
            ps = exact_dot("ntr,tro->nto", x_bits[j], w_bits[b])
            a = 2.0 * ps - rowsum[j][:, :, None]
            pt = jnp.where(a >= alpha, 1.0, jnp.where(a <= -alpha, -1.0, 0.0))
            sf = p["sf_q"][:, j, b, :]                          # (T, O)
            return acc + 0.5 * sig[j] * kap[b] * dot("nto,to->no", pt, sf,
                                                     sf_mode)

        acc = jax.lax.fori_loop(0, n_a * n_w, pair,
                                jnp.zeros((ROW_BLOCK, o), jnp.float32))
        c_w = sum(kappa)
        return acc + 0.5 * c_w * jnp.sum(xb, axis=-1, keepdims=True)

    y = jax.lax.map(block, xp.reshape(nb, ROW_BLOCK, t * r))
    return y.reshape(nb * ROW_BLOCK, o)[:n] * s_x * p["s_w"]


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + NORM_EPS) * scale


def _rope(x, theta: float):
    """Rotate-half RoPE of (S, H, D) at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, D/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, window: int, mode: str):
    """Causal grouped-query attention of (S, H, D) queries."""
    s, h, d = q.shape
    hk = k.shape[1]
    qg = q.reshape(s, hk, h // hk, d)
    logits = dot("skgd,tkd->kgst", qg, k, mode) / math.sqrt(d)
    pos = jnp.arange(s)
    mask = pos[None, :] <= pos[:, None]
    if window:
        mask &= pos[None, :] > pos[:, None] - window
    probs = jax.nn.softmax(jnp.where(mask, logits, -jnp.inf), axis=-1)
    return dot("kgst,tkd->skgd", probs, v, mode).reshape(s, h * d)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(frozen: tuple, w: Dict, tokens, mode: str):
    cfg = dict(frozen)
    cfg["psq"] = dict(cfg["psq"])
    h, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    s = tokens.shape[0]
    lin = functools.partial(psq_linear, cfg=cfg, mode=mode)
    layer_keys = ("norm1", "norm2", "wq", "wk", "wv", "wo", "gate", "up",
                  "down")

    def layer(x, lw):
        a = _rmsnorm(x, lw["norm1"])
        q = _rope(lin(a, lw["wq"]).reshape(s, h, hd), cfg["rope_theta"])
        k = _rope(lin(a, lw["wk"]).reshape(s, hk, hd), cfg["rope_theta"])
        v = lin(a, lw["wv"]).reshape(s, hk, hd)
        att = _attention(q, k, v, cfg["sliding_window"] or 0, mode)
        x = x + lin(att, lw["wo"])
        m = _rmsnorm(x, lw["norm2"])
        g = lin(m, lw["gate"])
        return x + lin(jax.nn.silu(g) * lin(m, lw["up"]), lw["down"]), None

    x, _ = jax.lax.scan(layer, w["embed"][tokens],
                        {key: w[key] for key in layer_keys})
    return dot("sd,dv->sv", _rmsnorm(x, w["final_norm"]), w["head"], mode)


MODEL_KEYS = ("num_hidden_layers", "hidden_size", "intermediate_size",
              "num_attention_heads", "num_key_value_heads", "head_dim",
              "vocab_size", "rope_theta", "sliding_window")


def freeze(cfg: Dict) -> tuple:
    """The model's sizes as a hashable static argument."""
    return tuple((k, cfg[k]) for k in MODEL_KEYS) + (
        ("psq", tuple(sorted(cfg["psq"].items()))),)


def forward(cfg: Dict, w: Dict, tokens, mode: str = "highest"):
    """Logits (S, V) of the token sequence ``tokens`` (S,)."""
    return _forward(freeze(cfg), w, tokens, mode)
