"""Logical-axis sharding: one rules table instead of per-arch pjit specs.

Model code annotates activations with *logical* axis names
(``constrain(x, "batch", "seq", "embed")``); the launcher activates a
rules table mapping logical names to mesh axes. With no rules active
(unit tests, single CPU) every annotation is a no-op, so the same model
code runs everywhere:

    >>> import jax, jax.numpy as jnp
    >>> constrain(jnp.ones((4, 8)), "batch", "ffn").shape  # no rules: no-op
    (4, 8)
    >>> mesh = jax.sharding.AbstractMesh((2, 2), ("data", "model"))
    >>> with axis_rules(RULES_2D, mesh):
    ...     logical_to_pspec(["batch", "seq", "ffn"], shape=(4, 8, 16))
    PartitionSpec('data', None, 'model')

Divisibility-aware: a rule only applies if the dimension divides by the
mesh-axis size — otherwise the dimension is left unsharded rather than
relying on implicit padding (keeps the compiled collectives clean; the
few non-divisible cases — e.g. 24 heads on a 16-way model axis — fall
back to the feature-dim sharding of the surrounding projections):

    >>> with axis_rules(RULES_2D, mesh):
    ...     logical_to_pspec(["batch", "ffn"], shape=(4, 7))  # 7 % 2 != 0
    PartitionSpec('data',)

Tensor-parallel PSQ serving rides the same table: the ``sf_out`` rule
maps every output-column-sized dimension of a packed layer (weight
codes, int4 planes, DCiM scale factors, bias) to the ``model`` mesh axis
— the JAX analogue of assigning crossbar columns plus their digital-CiM
scale-factor slices to different dies. :func:`packed_layer_pspecs`
derives the per-leaf specs and :func:`tp_axes` tells the serving matmul
(``core.psq_linear``) whether the active rules call for a sharded
(shard_map + psum) execution.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, Tuple[str, ...], None]

# logical axis -> mesh axis (or tuple of axes) tables
RULES_2D: Dict[str, MeshAxes] = {
    "batch": "data",
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "qkv_features": "model",
    "ffn": "model",
    "vocab": "model",
    "experts": "model",
    "expert_ffn": "model",
    "ssm_inner": "model",
    "kv_seq": None,        # decode KV cache sequence dim
    "long_kv_seq": "data",  # 500k-context decode: cache sharded over data
    "kv_blocks": "data",   # paged KV page pool: pages spread over data
    # per-slot recurrent state pools (SSM/xLSTM/hybrid: ssm states, mLSTM
    # C/n/m, sLSTM scalars, conv buffers) — slot axis shards like KV slots
    "recurrent_state": "data",
    "sf_out": "model",     # PSQ scale-factor column dim (follows weight out)
    "ktiles": None,
}

RULES_3D: Dict[str, MeshAxes] = dict(RULES_2D, batch=("pod", "data"))

# Expert-parallel serving: a dedicated ``expert`` mesh axis owns the
# expert dim of MoE FFN stacks (router stays replicated; activations
# inside an expert shard still follow the 2-D table). Activated by the
# engine / launcher for meshes that carry an ``expert`` axis
# (``--mesh DATA,MODEL,EXPERT``); :func:`expert_axes` is the query the
# MoE layer uses to pick the shard_map execution.
RULES_EXPERT: Dict[str, MeshAxes] = dict(RULES_2D, experts="expert")

# names of the raw expert-stacked weight leaves of a MoE block
# (repro.models.moe.init_moe) — the leaves expert placement targets
_EXPERT_WEIGHT_KEYS = ("w_gate", "w_up", "w_down")


class _State(threading.local):
    def __init__(self):
        self.rules: Optional[Dict[str, MeshAxes]] = None
        self.mesh: Optional[Mesh] = None


_STATE = _State()


@contextlib.contextmanager
def axis_rules(rules: Dict[str, MeshAxes], mesh: Optional[Mesh] = None):
    prev = (_STATE.rules, _STATE.mesh)
    _STATE.rules, _STATE.mesh = rules, mesh
    try:
        yield
    finally:
        _STATE.rules, _STATE.mesh = prev


def active_rules() -> Optional[Dict[str, MeshAxes]]:
    return _STATE.rules


def _axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def logical_to_pspec(
    logical: Sequence[Optional[str]],
    shape: Optional[Sequence[int]] = None,
    rules: Optional[Dict[str, MeshAxes]] = None,
    mesh: Optional[Mesh] = None,
) -> P:
    """Map logical axis names to a PartitionSpec under the active rules."""
    rules = rules if rules is not None else _STATE.rules
    mesh = mesh if mesh is not None else _STATE.mesh
    if rules is None:
        return P()
    spec = []
    used = set()
    for i, name in enumerate(logical):
        ax = rules.get(name) if name is not None else None
        if ax is not None and mesh is not None and shape is not None:
            if shape[i] % _axis_size(mesh, ax) != 0:
                ax = None  # divisibility guard
        if ax is not None:
            axes = (ax,) if isinstance(ax, str) else tuple(ax)
            if any(a in used for a in axes):
                ax = None  # each mesh axis shards at most one dim
            else:
                used.update(axes)
        spec.append(ax)
    while spec and spec[-1] is None:
        spec.pop()
    return P(*spec)


def constrain(x: jax.Array, *logical: Optional[str]) -> jax.Array:
    """Apply a logical sharding constraint (no-op without active rules).

    Empty arrays (e.g. the shared-attention KV of a hybrid with no
    attention groups) hold nothing to place and pass through: an eager
    constraint on one comes back replicated, which JAX rejects.
    """
    if _STATE.rules is None or x.size == 0:
        return x
    spec = logical_to_pspec(logical, shape=x.shape)
    if _STATE.mesh is not None:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_STATE.mesh, spec)
        )
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Packed-layer specs (tensor-parallel PSQ serving)
# ---------------------------------------------------------------------------
#
# A PackedLayer (repro.serve.cache) is the weight-stationary state of one
# crossbar-programmed linear: weight codes (K, O), optional int4 planes
# (K/2, O), DCiM scale factors (T, n_a, n_w, O or 1), plus scalars. Its
# natural tensor-parallel split is COLUMN-wise — each device owns a
# contiguous slice of output columns and the matching scale-factor
# columns, exactly as HCiM assigns crossbar columns + their digital CiM
# slices to arrays. Every column-sized dim maps to the logical ``sf_out``
# axis; everything else is replicated. Scan-stacked layers (leading
# layer axis) get a leading ``None``.

def packed_layer_pspecs(layer: Any, rules: Optional[Dict[str, MeshAxes]] = None,
                        mesh: Optional[Mesh] = None) -> Any:
    """PartitionSpec pytree for one packed layer under the (active) rules.

    Logical axes are assigned per field for the UNSTACKED rank —
    scan-stacked leaves (leading layer axis, ``w_codes.ndim == 3``) get a
    leading ``None``; ``s_w`` is () for the per-layer LSQ step and
    ("sf_out",) for the per-channel variant, disambiguated through the
    stacking of ``w_codes`` (base rank 2).

    The divisibility guard of :func:`logical_to_pspec` applies per leaf:
    an output dim that does not divide the ``model`` axis — or the size-1
    trailing dim of a reduced-granularity ``sf_q`` — stays replicated.
    The result has the same pytree structure as ``layer`` (spec leaves),
    so it can feed ``shard_map`` in_specs or ``NamedSharding`` placement
    directly.
    """
    rules = rules if rules is not None else (_STATE.rules or RULES_2D)
    mesh = mesh if mesh is not None else _STATE.mesh
    stacked = layer.w_codes.ndim == 3
    col = "sf_out"

    def spec(arr, logical):
        if arr is None:
            return None
        names = [None] * (arr.ndim - len(logical)) + list(logical)
        return logical_to_pspec(names, shape=arr.shape, rules=rules, mesh=mesh)

    s_w_logical = (col,) if layer.s_w.ndim - int(stacked) == 1 else ()
    return type(layer)(
        cfg=layer.cfg,
        w_codes=spec(layer.w_codes, (None, col)),
        s_w=spec(layer.s_w, s_w_logical),
        sf_q=spec(layer.sf_q, (None, None, None, col)),
        alpha=spec(layer.alpha, ()),
        step_x=spec(layer.step_x, ()),
        sigma=spec(layer.sigma, (None,)),
        kappa=spec(layer.kappa, (None,)),
        w_packed=spec(layer.w_packed, (None, col)),
        bias=spec(layer.bias, (col,)),
        # occupancy is pytree AUX data, not a leaf: the spec tree must
        # carry the identical value or tree.map(layer, specs) rejects the
        # structure mismatch. (Each TP shard still runs dense — the global
        # metadata fails the per-shard shape guard by design.)
        occupancy=getattr(layer, "occupancy", None),
    )


def shard_packed_layer(layer: Any, mesh: Mesh,
                       rules: Optional[Dict[str, MeshAxes]] = None) -> Any:
    """Place one packed layer's leaves on ``mesh`` column-sharded.

    A plain ``device_put`` per leaf with the :func:`packed_layer_pspecs`
    sharding — the one-time serving-cache placement step (re-placing an
    already-placed layer is a no-op transfer).
    """
    rules = rules if rules is not None else RULES_2D
    specs = packed_layer_pspecs(layer, rules=rules, mesh=mesh)
    return jax.tree.map(
        lambda a, s: jax.device_put(a, NamedSharding(mesh, s)), layer, specs
    )


def shard_packed_tree(tree: Any, mesh: Mesh,
                      rules: Optional[Dict[str, MeshAxes]] = None) -> Any:
    """Recursively place every packed layer in a served param tree.

    Non-packed nodes (embeddings, norms, plain param dicts) pass through
    untouched — they stay replicated under the jitted serving step.
    """
    if hasattr(tree, "apply_serving"):
        return shard_packed_layer(tree, mesh, rules)
    if isinstance(tree, dict):
        return {k: shard_packed_tree(v, mesh, rules) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return type(tree)(shard_packed_tree(v, mesh, rules) for v in tree)
    return tree


def expert_axes() -> Optional[Tuple[Mesh, str]]:
    """The (mesh, axis name) expert parallelism is active on, else None.

    The expert-parallel analogue of :func:`tp_axes`: active when a rules
    table is installed with a REAL mesh, the table maps the logical
    ``experts`` axis to a single mesh axis (``RULES_EXPERT``), and that
    axis has size > 1. ``repro.models.moe.apply_moe`` consults this to
    decide between the single-device dispatch and the shard_map
    expert-parallel execution.
    """
    rules, mesh = _STATE.rules, _STATE.mesh
    if rules is None or not isinstance(mesh, Mesh):
        return None
    ax = rules.get("experts")
    if not isinstance(ax, str) or mesh.shape.get(ax, 1) <= 1:
        return None
    return mesh, ax


def rules_for_mesh(mesh: Optional[Mesh]) -> Dict[str, MeshAxes]:
    """Pick the default rules table for a mesh by its axis names.

    A mesh carrying an ``expert`` axis gets :data:`RULES_EXPERT`
    (expert-parallel MoE next to the usual data x model rules); a
    ``pod`` axis gets :data:`RULES_3D`; anything else — including no
    mesh — the 2-D table.
    """
    names = set(getattr(mesh, "axis_names", ()) or ())
    if "expert" in names:
        return RULES_EXPERT
    if "pod" in names:
        return RULES_3D
    return RULES_2D


def shard_expert_params(tree: Any, mesh: Mesh,
                        rules: Optional[Dict[str, MeshAxes]] = None) -> Any:
    """Place raw expert-stacked MoE weights over the ``experts`` axis.

    Walks a (served) param tree and ``device_put``s every
    ``w_gate``/``w_up``/``w_down`` leaf with its expert dim — position
    ``ndim - 3``, which holds for both per-layer ``(E, d, ff)`` and
    scan-stacked ``(L, E, d, ff)`` stacks — on the rules' ``experts``
    mesh axis. Router weights, PSQ quantizer states and every non-MoE
    node pass through replicated (untouched). Leaves whose expert count
    does not divide the axis stay replicated too (the divisibility
    story of the rules table).
    """
    rules = rules if rules is not None else RULES_EXPERT
    ax = rules.get("experts")
    if not isinstance(ax, str) or mesh.shape.get(ax, 1) <= 1:
        return tree

    def place(node: Any) -> Any:
        if isinstance(node, dict):
            out = {}
            for k, v in node.items():
                if (k in _EXPERT_WEIGHT_KEYS and hasattr(v, "ndim")
                        and v.ndim >= 3
                        and v.shape[v.ndim - 3] % mesh.shape[ax] == 0):
                    spec = [None] * v.ndim
                    spec[v.ndim - 3] = ax
                    out[k] = jax.device_put(v, NamedSharding(mesh, P(*spec)))
                else:
                    out[k] = place(v)
            return out
        if isinstance(node, (list, tuple)):
            return type(node)(place(v) for v in node)
        return node

    return place(tree)


def tp_axes() -> Optional[Tuple[Mesh, str]]:
    """The (mesh, axis name) tensor parallelism is active on, else None.

    Active means: a rules table is installed with a REAL mesh (shard_map
    cannot run on an AbstractMesh), the table maps the PSQ column axis
    ``sf_out`` to a single mesh axis, and that axis has size > 1. The
    serving matmul consults this to decide between the single-device and
    the shard_map + psum execution of a packed layer.
    """
    rules, mesh = _STATE.rules, _STATE.mesh
    if rules is None or not isinstance(mesh, Mesh):
        return None
    ax = rules.get("sf_out")
    if not isinstance(ax, str) or mesh.shape.get(ax, 1) <= 1:
        return None
    return mesh, ax


def data_pspec(ndim: int, shape: Sequence[int],
               exclude: Tuple[str, ...] = ()) -> P:
    """Leading-axis batch spec for an activation under the active rules.

    The leading dim follows the ``batch`` rule (divisibility-guarded);
    all other dims stay replicated. ``exclude`` drops mesh axes that the
    caller already uses manually (e.g. the tensor-parallel axis inside a
    ``shard_map``).
    """
    rules, mesh = _STATE.rules, _STATE.mesh
    if rules is None:
        return P()
    ax = rules.get("batch")
    if isinstance(ax, str) and ax in exclude:
        ax = None
    if isinstance(ax, tuple):
        ax = tuple(a for a in ax if a not in exclude) or None
    guarded = dict(rules, batch=ax)
    return logical_to_pspec(
        ["batch"] + [None] * (ndim - 1), shape=shape, rules=guarded, mesh=mesh
    )
