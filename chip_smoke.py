#!/usr/bin/env python3
"""Chip smoke test: serve tinyllama-1.1b at its published widths through
the PSQ-packed HCiM datapath on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # 2x2 (data x model) mesh vs one device

One chip: seeded weights are packed once (``pack_tree_psq``, compiled
``pallas`` backend) and served through ``ServeEngine``'s continuous
scheduler, as ``python -m repro.launch.serve --psq-packed`` does. Then
the prefill logits of a short seeded batch are compared between the
compiled kernels and the ``reference`` oracle on the same packed weights,
and one layer of each projection shape is compared the same way with a
quarter of its weight blocks zeroed, so the sparsity-skip flags are
exercised too.

``--chips 4`` runs only the multi-chip phase: the same model and prompts
served tensor- and data-parallel on a ``2,2`` mesh and on one device in
the same process; placement, first-step logits and greedy tokens are
compared.

Exits non-zero, printing no result, without a TPU or without the repo's
``src/`` beside it. On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "tinyllama-1.1b"
SEED = 0
SLOTS = 8
MAX_LEN = 1024
N_REQUESTS = 8
PROMPT_LEN = (128, 512)
NEW_TOKENS = 32
LOGITS_BATCH = (4, 128)

# Bound on the relative L2 difference of prefill logits between the
# compiled kernels and the reference oracle on the same packed weights.
# The bit-plane partial sums are exact on both paths; only the order of
# the f32 scale-factor accumulation differs. On one v5e at these widths
# and this batch the clean difference is 1.5e-6; planted kernel faults
# gave: scale factors accumulated in bf16 9.5e-5, one weight slab
# dropped in every kernel call 2.9e-3, layer order reversed 2.2e-3. The
# bound sits 13x above the clean reading and 4.7x below the smallest
# fault.
REL_L2_TOL = 2e-5
# Bound on the same difference between a 2,2 mesh and one device, both
# on the compiled kernels. It is there to catch placement and collective
# faults (a shard gathered in the wrong place, a missing reduction),
# which are gross: they move the logits as far as reversing the layers
# does (2.2e-3 above), or further. The two programs are compiled apart,
# so an activation can land on the other side of a quantizer rounding
# boundary in one of them and shift later positions by a code step; the
# bound leaves room for that, 10x below the gross faults.
MESH_REL_L2_TOL = 2e-4
# One layer on identical integer inputs: only the f32 accumulation order
# of at most 44 tiles x 16 plane pairs differs, a few ulp of the output
# (at most 1.2e-6 max rel on one v5e over tinyllama's four (K, O) pairs;
# on the CPU the bf16 scale-factor fault gave 1.1e-4 and a dropped slab
# 0.34).
LAYER_REL_TOL = 1e-5


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def seeded_params(cfg, backend: str, seed: int = SEED):
    """(cfg with a PSQ QuantConfig on ``backend``, float32 master params)."""
    import jax

    from repro.core.config import PSQ_TERNARY
    from repro.models import init_model

    qcfg = dataclasses.replace(PSQ_TERNARY, kernel_backend=backend)
    cfg = cfg.with_quant(qcfg)
    return cfg, init_model(jax.random.PRNGKey(seed), cfg)


def with_backend(tree, backend: str):
    """The same packed tree, its PSQ layers dispatched to ``backend``."""
    import jax

    from repro.serve.cache import PackedLayer

    def swap(node):
        if isinstance(node, PackedLayer):
            return dataclasses.replace(node, cfg=dataclasses.replace(
                node.cfg, kernel_backend=backend))
        return node

    return jax.tree.map(swap, tree,
                        is_leaf=lambda n: isinstance(n, PackedLayer))


def make_prompts(n: int, lo: int, hi: int, vocab: int, seed: int = SEED):
    """``n`` seeded prompts with lengths in [lo, hi]: the first half in
    (hi/4, hi/2], the rest in (hi/2, hi], grouped so the scheduler admits
    them in two full prefill waves of one length bucket each."""
    import numpy as np

    rng = np.random.RandomState(seed)
    half = n // 2
    lens = np.concatenate([
        rng.randint(max(lo, hi // 4 + 1), hi // 2 + 1, size=half),
        rng.randint(hi // 2 + 1, hi + 1, size=n - half),
    ])
    return [rng.randint(0, vocab, size=int(n_tok)).astype(np.int32)
            for n_tok in lens]


class CompileClock:
    """Seconds the XLA backend spends compiling while active. Tracing is
    left out: its events nest (a kernel body is traced inside the model's
    trace), so their sum over-counts."""

    _EVENTS = ("backend_compile_duration",)

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, name, secs, **_):
        if name.rsplit("/", 1)[-1] in self._EVENTS:
            self.seconds += secs


def serve(packed, cfg, prompts, *, new_tokens: int, slots: int,
          max_len: int, mesh=None, clock: CompileClock = None):
    """Serve ``prompts`` to completion through ``ServeEngine``; returns
    (engine, finished requests, wall seconds, compile seconds)."""
    from repro.serve import EngineConfig, ServeEngine

    eng = ServeEngine(packed, cfg,
                      EngineConfig(max_batch=slots, max_len=max_len,
                                   mode="continuous"),
                      mesh=mesh)
    for p in prompts:
        eng.submit(p, max_new_tokens=new_tokens)
    c0 = clock.seconds if clock else 0.0
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    return eng, done, wall, (clock.seconds - c0) if clock else 0.0


def check_finished(done, n: int, new_tokens: int) -> None:
    if len(done) != n:
        fail(f"{len(done)} of {n} requests finished")
    short = [(r.uid, len(r.output)) for r in done
             if len(r.output) != new_tokens]
    if short:
        fail(f"requests without their {new_tokens}-token budget "
             f"(uid, tokens): {short}")


def prefill_logits(packed, cfg, tokens, *, mesh=None, last_only=False):
    """Logits of a prefill forward, under the mesh's sharding rules."""
    import contextlib

    import jax
    import numpy as np

    from repro.models import forward
    from repro.parallel.sharding import axis_rules, rules_for_mesh

    ctx = (axis_rules(rules_for_mesh(mesh), mesh) if mesh is not None
           else contextlib.nullcontext())
    with ctx:
        fn = jax.jit(lambda p, t: forward(p, cfg, {"tokens": t},
                                          last_only=last_only)[0])
        return np.asarray(fn(packed, tokens), np.float32)


def compare(got, want) -> dict:
    """Max abs / max rel / relative-L2 difference and argmax agreement."""
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    diff = np.abs(got - want)
    return {
        "max_abs": float(diff.max()),
        "max_rel": float(diff.max() / max(np.abs(want).max(), 1e-30)),
        "rel_l2": float(np.linalg.norm(got - want)
                        / max(np.linalg.norm(want), 1e-30)),
        "argmax_agree": float(np.mean(got.argmax(-1) == want.argmax(-1))),
    }


def logits_check(packed, cfg, batch, *, backend: str, seed: int = SEED):
    """Prefill logits of a seeded (B, T) batch: ``backend`` vs the
    ``reference`` oracle on the same packed weights. Both runs use
    float32 matmuls everywhere else, so only the PSQ layers differ."""
    import jax

    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), batch, 0,
                                cfg.vocab_size)
    ref_cfg = cfg.with_quant(dataclasses.replace(cfg.quant,
                                                 kernel_backend="reference"))
    with jax.default_matmul_precision("highest"):
        got = prefill_logits(with_backend(packed, backend), cfg, tokens)
        want = prefill_logits(with_backend(packed, "reference"), ref_cfg,
                              tokens)
    return compare(got, want)


def layer_check(layer, *, backend: str, rows: int, seed: int = SEED):
    """One packed layer on seeded integer activations, ``backend`` vs
    ``reference``, with a quarter of its (tile, column-block) weight slabs
    zeroed so the kernel's sparsity-skip flags are live."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import registry
    from repro.kernels.occupancy import column_occupancy

    cfg = layer.cfg
    spec, r = cfg.spec, cfg.xbar_rows
    codes = np.array(layer.w_codes, np.float32)           # (K, O)
    k, o = codes.shape
    blk = 128                                  # kernel column block
    for t in range(-(-k // r)):
        for c in range(-(-o // blk)):
            if (t + c) % 4 == 0:
                codes[t * r:(t + 1) * r, c * blk:(c + 1) * blk] = 0.0
    occ = column_occupancy(codes, xbar_rows=r, n_w=spec.n_bits_w)
    x = jax.random.randint(jax.random.PRNGKey(seed + 2), (rows, k),
                           spec.a_qn, spec.a_qp + 1).astype(jnp.float32)
    kw = dict(n_a=spec.n_bits_a, n_w=spec.n_bits_w, levels=cfg.psq_levels,
              adc_bits=cfg.adc_bits, xbar_rows=r, fuse_planes=cfg.fuse_planes,
              occupancy=occ)
    args = (x, jnp.asarray(codes), layer.sf_q, layer.alpha)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(functools.partial(
            registry.get_backend(backend).psq_matmul, **kw))(*args)
        want = jax.jit(functools.partial(
            registry.get_backend("reference").psq_matmul, **kw))(*args)
    out = compare(got, want)
    out["zero_block_share"] = float(np.mean(occ.zero_blocks_np()))
    return out


def device_info() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes() -> int:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def first_layer(packed, name: str):
    """Layer 0 of the scan-stacked packed projection ``name``."""
    import jax

    node = packed["blocks"]
    for key in name.split("/"):
        node = node[key]
    return jax.tree.map(lambda a: a[0], node)


def one_chip(cfg, clock: CompileClock) -> None:
    import jax

    from repro.serve import PackedModelCache, pack_tree_psq, throughput_stats

    backend = "pallas"
    print(f"[chip_smoke] {cfg.name}: layers {cfg.n_layers}, d_model "
          f"{cfg.d_model}, heads {cfg.n_heads}/{cfg.n_kv_heads} x "
          f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}; backend {backend}")
    t0 = time.perf_counter()
    cfg, params = seeded_params(cfg, backend)
    cache = PackedModelCache()
    packed = pack_tree_psq(params, cfg.quant, cache)
    del params
    jax.block_until_ready(packed)
    print(f"[chip_smoke] setup (init + pack {cache.stats()['layers']} "
          f"projections) {time.perf_counter() - t0:.3f} s")

    prompts = make_prompts(N_REQUESTS, *PROMPT_LEN, cfg.vocab_size)
    eng, done, wall, comp = serve(packed, cfg, prompts,
                                  new_tokens=NEW_TOKENS, slots=SLOTS,
                                  max_len=MAX_LEN, clock=clock)
    check_finished(done, N_REQUESTS, NEW_TOKENS)
    tok = sum(len(r.output) for r in done)
    print(f"[chip_smoke] served {len(done)} requests (prompts "
          f"{sorted(len(p) for p in prompts)}), {tok} tokens emitted; "
          f"wall {wall:.3f} s incl. compile {comp:.3f} s; "
          f"prefill calls {eng.prefill_calls}, decode steps "
          f"{eng.decode_steps}")
    print(f"[chip_smoke] throughput_stats {throughput_stats(done)}")
    print(f"[chip_smoke] peak_bytes_in_use after serving {peak_bytes()}")
    del eng

    problems = []
    c0, t0 = clock.seconds, time.perf_counter()
    res = logits_check(packed, cfg, LOGITS_BATCH, backend=backend)
    print(f"[chip_smoke] prefill logits {LOGITS_BATCH} {backend} vs "
          f"reference: {res}; {time.perf_counter() - t0:.3f} s incl. "
          f"compile {clock.seconds - c0:.3f} s")
    if not res["rel_l2"] <= REL_L2_TOL:
        problems.append(f"pallas vs reference logits rel L2 "
                        f"{res['rel_l2']} > {REL_L2_TOL}")
    # tinyllama's four (K, O) pairs at decode rows, one at prefill rows
    for name, rows in (("attn/wq", 8), ("attn/wk", 8), ("mlp/up", 8),
                       ("mlp/down", 8), ("mlp/up", 512)):
        res = layer_check(first_layer(packed, name), backend=backend,
                          rows=rows)
        print(f"[chip_smoke] layer 0 {name} at {rows} rows, skip flags "
              f"on: {res}")
        if not res["max_rel"] <= LAYER_REL_TOL:
            problems.append(f"{name}: pallas vs reference max rel "
                            f"{res['max_rel']} > {LAYER_REL_TOL}")
    print(f"[chip_smoke] peak_bytes_in_use {peak_bytes()}")
    if problems:
        fail("; ".join(problems))


def split(a, axis: int) -> int:
    """Into how many shards ``a``'s dimension ``axis`` is cut: 1 when that
    dimension is replicated, whatever the number of devices holding it."""
    return a.shape[axis] // a.sharding.shard_shape(a.shape)[axis]


def mesh_phase(cfg, *, backend: str, mesh_shape=(2, 2),
               n_requests: int = N_REQUESTS, prompt_len=PROMPT_LEN,
               new_tokens: int = NEW_TOKENS, slots: int = SLOTS,
               max_len: int = MAX_LEN, logits_batch=LOGITS_BATCH,
               clock: CompileClock = None) -> dict:
    """Serve the same seeded model and prompts on a (data, model) mesh
    and on one device; returns placement, logit and token comparisons."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.serve import PackedModelCache, pack_tree_psq
    from repro.serve.cache import PackedLayer

    mesh = make_mesh(mesh_shape, ("data", "model"))
    n_dev = mesh.devices.size
    cfg, params = seeded_params(cfg, backend)
    cache = PackedModelCache()
    single = pack_tree_psq(params, cfg.quant, cache)
    sharded = pack_tree_psq(params, cfg.quant, cache, mesh=mesh)
    del params

    prompts = make_prompts(n_requests, *prompt_len, cfg.vocab_size)
    runs = {}
    for name, tree, m in (("mesh", sharded, mesh), ("single", single, None)):
        eng, done, wall, comp = serve(tree, cfg, prompts,
                                      new_tokens=new_tokens, slots=slots,
                                      max_len=max_len, mesh=m, clock=clock)
        check_finished(done, n_requests, new_tokens)
        kv = eng._cache["kv"]
        runs[name] = {
            "tokens": {r.uid: list(r.output) for r in done},
            "wall_s": wall, "compile_s": comp,
            "kv_devices": min(len(kv[s].sharding.device_set) for s in kv),
            # (n_layers, slots, max_len, kv_heads, head_dim) pools
            "kv_slot_split": min(split(kv[s], 1) for s in kv),
        }
        del eng
    codes = [n.w_codes for n in jax.tree.leaves(
        sharded, is_leaf=lambda n: isinstance(n, PackedLayer))
        if isinstance(n, PackedLayer)]
    tokens = jax.random.randint(jax.random.PRNGKey(SEED + 1), logits_batch,
                                0, cfg.vocab_size)
    got = prefill_logits(sharded, cfg, tokens, mesh=mesh, last_only=True)
    want = prefill_logits(single, cfg, tokens, last_only=True)
    ref, out = runs["single"]["tokens"], runs["mesh"]["tokens"]
    agree = sum(a == b for u in ref for a, b in zip(ref[u], out[u]))
    first_div = {u: next((i for i, (a, b) in enumerate(zip(ref[u], out[u]))
                          if a != b), None) for u in ref}
    return {
        "devices": n_dev,
        "mesh": dict(mesh.shape),
        "packed_layer_devices": min(len(a.sharding.device_set)
                                    for a in codes),
        # every projection's codes column-split over "model"
        "packed_layer_split": min(split(a, -1) for a in codes),
        "kv_pool_devices": runs["mesh"]["kv_devices"],
        "kv_slot_split": runs["mesh"]["kv_slot_split"],
        "first_step_logits": compare(got, want),
        "tokens_agree": agree,
        "tokens_total": sum(len(v) for v in ref.values()),
        "requests_identical": sum(ref[u] == out[u] for u in ref),
        "first_divergence": {u: i for u, i in first_div.items()
                             if i is not None},
        "wall_s": {k: v["wall_s"] for k, v in runs.items()},
        "compile_s": {k: v["compile_s"] for k, v in runs.items()},
    }


def four_chips(cfg, clock: CompileClock) -> None:
    import jax

    if len(jax.devices()) < 4:
        fail(f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    t0 = time.perf_counter()
    res = mesh_phase(cfg, backend="pallas", clock=clock)
    print(f"[chip_smoke] 2,2 mesh vs one device, {cfg.name} published "
          f"widths, backend pallas ({time.perf_counter() - t0:.3f} s "
          f"total): {json.dumps(res)}")
    print(f"[chip_smoke] peak_bytes_in_use (device 0) {peak_bytes()}")
    mesh = res["mesh"]
    if res["packed_layer_devices"] != 4 or res["kv_pool_devices"] != 4:
        fail("packed layers or KV pool not spread over all 4 devices")
    if res["packed_layer_split"] != mesh["model"]:
        fail(f"a packed layer's codes are not split {mesh['model']} ways "
             f"over 'model' (split {res['packed_layer_split']})")
    if res["kv_slot_split"] != mesh["data"]:
        fail(f"the KV pool's slots are not split {mesh['data']} ways over "
             f"'data' (split {res['kv_slot_split']})")
    # Logits decide. Greedy tokens are reported, not checked: the two
    # programs are compiled apart, so a near-tie in the argmax over the
    # vocabulary can flip on rounding noise alone and then every later
    # token of that request differs.
    if not res["first_step_logits"]["rel_l2"] <= MESH_REL_L2_TOL:
        fail(f"mesh vs single first-step logits rel L2 "
             f"{res['first_step_logits']['rel_l2']} > {MESH_REL_L2_TOL}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 2,2 mesh vs one-device phase")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not beside this script ({ROOT}/src)")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        fail(f"needs a TPU; JAX found {platform!r} devices")

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[chip_smoke] compile cache {enable_compile_cache()}")
    clock = CompileClock()
    cfg = get_config(ARCH)
    if args.chips == 4:
        four_chips(cfg, clock)
    else:
        one_chip(cfg, clock)
    print(json.dumps({"ok": True, "device": device_info()}))


if __name__ == "__main__":
    main()
