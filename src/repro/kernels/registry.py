"""Kernel backend registry — one dispatch point for the HCiM datapath.

Every implementation of the integer-level PSQ pipeline (and of the int4
weight-stationary decode matmul) registers here under a name; callers
select a backend per call, per config (``QuantConfig.kernel_backend``) or
process-wide (``set_default_backend`` / ``REPRO_KERNEL_BACKEND``), and
the rest of the stack — ``kernels.ops``, ``core.psq_linear``, the serving
cache, ``benchmarks/kernel_bench.py`` — never hard-codes an
implementation again.

Built-in backends:

  reference        pure-jnp oracle (:mod:`repro.kernels.ref`) — bit-exact
                   semantics, always available, the conformance baseline.
  pallas-interpret Pallas kernels in interpret mode — runs anywhere
                   (CPU containers included), exercises the real kernel
                   code path minus Mosaic lowering.
  pallas           compiled Pallas kernels — TPU only; the serving
                   fast path.

With nothing pinned, the default follows the platform: compiled
``pallas`` on a TPU, ``pallas-interpret`` elsewhere (the only Pallas a
CPU has). A TPU run reaches the interpreter or the ``reference`` oracle
only when a caller names them.

Backends expose three entry points with fixed signatures:

  psq_matmul(x_int, w_int, sf_q, alpha, *, n_a, n_w, levels, adc_bits,
             xbar_rows, fuse_planes=False,
             occupancy=None) -> y_int                      (B, O)
  int4_matmul(x, w_packed, scale) -> y                     (B, O)
  paged_attention(q, k_pool, v_pool, block_tables, lengths,
                  k_new, v_new) -> ctx                     (B, H, D)

``x_int``/``w_int`` are integer-valued f32 codes, ``sf_q`` the
dequantized fixed-point scale factors broadcastable to
``(T, n_a, n_w, O)`` — exactly the contract of
:func:`repro.kernels.ref.psq_matmul_ref`. ``occupancy`` is optional
pack-time sparsity metadata (:mod:`repro.kernels.occupancy`); backends
may skip all-zero ternary column blocks with it, but must stay
bit-exact against the reference oracle whether or not they do. ``paged_attention`` is the
single-token decode attention over the paged KV pool (block-table
indirection; contract in :mod:`repro.kernels.paged_attention`) — it is
optional for third-party backends (``None`` means not implemented, and
``models.decode.decode_step_paged`` falls back to its inline gather
path when no backend is requested).

Example — look up the conformance oracle and check what's registered:

    >>> from repro.kernels import registry
    >>> registry.get_backend("reference").name
    'reference'
    >>> all(b in registry.registered_backends()
    ...     for b in ("reference", "pallas-interpret", "pallas"))
    True

Per-shard dispatch under tensor parallelism
-------------------------------------------
The serving TP path (``core.psq_linear.serve_linear_tp``) calls the
backend *inside* a ``shard_map`` body, so ``psq_matmul`` sees the LOCAL
problem — ``(B, K) x (K, O/n)`` for an ``n``-way column split — and a
Pallas backend lowers one kernel per device over its own column block
(GSPMD cannot partition a ``pallas_call``; manual sharding is how the
kernels scale out). Resolution is shape-independent, so the same
selection order applies per shard; callers resolve once *before*
entering the mapped trace to fail fast on unavailable backends (see
:func:`resolve_backend`). :func:`describe` gives launchers a one-line
availability table for logs and bench metadata.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Callable, Dict, List, Optional

import jax

__all__ = [
    "KernelBackend",
    "available_backends",
    "default_backend",
    "describe",
    "get_backend",
    "register_backend",
    "registered_backends",
    "resolve_backend",
    "set_default_backend",
]

_ENV_VAR = "REPRO_KERNEL_BACKEND"


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """A named implementation of the HCiM kernel contract."""

    name: str
    description: str
    psq_matmul: Callable[..., jax.Array]
    int4_matmul: Callable[..., jax.Array]
    # availability is queried lazily: it can depend on jax.default_backend()
    is_available: Callable[[], bool] = lambda: True
    # optional paged-decode attention (kernels/paged_attention.py contract)
    paged_attention: Optional[Callable[..., jax.Array]] = None

    def require_available(self) -> "KernelBackend":
        if not self.is_available():
            raise RuntimeError(
                f"kernel backend {self.name!r} is registered but not "
                f"available on the {jax.default_backend()!r} platform "
                f"(available: {available_backends()})"
            )
        return self


_REGISTRY: Dict[str, KernelBackend] = {}
# None: follow the platform (see default_backend)
_DEFAULT_NAME: Optional[str] = None


def register_backend(backend: KernelBackend) -> KernelBackend:
    """Add (or replace) a backend; returns it so use as a statement or fn.

    A new implementation only has to satisfy the two-entry-point
    contract; the conformance suite and ``benchmarks/kernel_bench.py``
    pick it up automatically::

        register_backend(KernelBackend(
            name="my-backend",
            description="what it is",
            psq_matmul=my_psq_matmul,
            int4_matmul=my_int4_matmul,
        ))
    """
    _REGISTRY[backend.name] = backend
    return backend


def registered_backends() -> List[str]:
    """All registered backend names, available on this platform or not.

    >>> "reference" in registered_backends()
    True
    """
    return sorted(_REGISTRY)


def describe() -> List[Dict[str, object]]:
    """Availability table: one row per registered backend.

    Stable name order; ``available`` is evaluated lazily against the
    current JAX platform. Launchers and benches embed this in their
    logs/JSON so a recorded run states which implementations it could
    have dispatched to.

    >>> rows = describe()
    >>> [r["name"] for r in rows] == registered_backends()
    True
    >>> all(set(r) == {"name", "description", "available"} for r in rows)
    True
    """
    return [
        {"name": n, "description": _REGISTRY[n].description,
         "available": _REGISTRY[n].is_available()}
        for n in sorted(_REGISTRY)
    ]


def available_backends() -> List[str]:
    """Backend names runnable on the current JAX platform.

    Always a subset of :func:`registered_backends`; the ``reference``
    oracle is available everywhere.

    >>> set(available_backends()) <= set(registered_backends())
    True
    >>> "reference" in available_backends()
    True
    """
    return [n for n in sorted(_REGISTRY) if _REGISTRY[n].is_available()]


def get_backend(name: Optional[str] = None) -> KernelBackend:
    """Look up a backend by name (``None`` -> the process default).

    Raises ``KeyError`` for unknown names and ``RuntimeError`` for
    backends that cannot run on the current platform.

    >>> get_backend("reference").name
    'reference'
    >>> get_backend("no-such-backend")  # doctest: +IGNORE_EXCEPTION_DETAIL
    Traceback (most recent call last):
        ...
    KeyError: unknown kernel backend 'no-such-backend'
    """
    resolved = name or default_backend()
    try:
        backend = _REGISTRY[resolved]
    except KeyError:
        raise KeyError(
            f"unknown kernel backend {resolved!r}; "
            f"registered: {registered_backends()}"
        ) from None
    return backend.require_available()


def set_default_backend(name: Optional[str]) -> None:
    """Process-wide default used when a config does not pin a backend.

    ``None`` restores the platform default. (``REPRO_KERNEL_BACKEND`` in
    the environment still beats this — the example sets it aside to
    show the in-process value, then restores.)

    >>> import os
    >>> saved = os.environ.pop("REPRO_KERNEL_BACKEND", None)
    >>> set_default_backend("reference")
    >>> default_backend()
    'reference'
    >>> set_default_backend(None)   # restore the platform default
    >>> if saved is not None: os.environ["REPRO_KERNEL_BACKEND"] = saved
    """
    global _DEFAULT_NAME
    if name is not None and name not in _REGISTRY:
        raise KeyError(
            f"unknown kernel backend {name!r}; "
            f"registered: {registered_backends()}"
        )
    _DEFAULT_NAME = name


def default_backend() -> str:
    """Env override (``REPRO_KERNEL_BACKEND``) beats the in-process
    default, which beats the platform's: compiled ``pallas`` on a TPU,
    ``pallas-interpret`` anywhere else.

    >>> import os
    >>> saved = os.environ.pop("REPRO_KERNEL_BACKEND", None)
    >>> default_backend() in registered_backends()
    True
    >>> if saved is not None: os.environ["REPRO_KERNEL_BACKEND"] = saved
    """
    return (os.environ.get(_ENV_VAR) or _DEFAULT_NAME
            or ("pallas" if _compiled_pallas_available()
                else "pallas-interpret"))


def resolve_backend(cfg) -> KernelBackend:
    """Backend for a :class:`repro.core.config.QuantConfig`.

    ``cfg.kernel_backend`` pins one explicitly; otherwise the process
    default applies. Accepts any object with a ``kernel_backend``
    attribute (or a plain name / None).

    >>> import os
    >>> saved = os.environ.pop("REPRO_KERNEL_BACKEND", None)
    >>> resolve_backend("reference").name
    'reference'
    >>> resolve_backend(None).name == default_backend()
    True
    >>> if saved is not None: os.environ["REPRO_KERNEL_BACKEND"] = saved
    """
    if cfg is None:
        return get_backend(None)
    if isinstance(cfg, str):
        return get_backend(cfg)
    return get_backend(getattr(cfg, "kernel_backend", None))


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------

def _reference_psq(x_int, w_int, sf_q, alpha, *, n_a, n_w, levels,
                   adc_bits=7, xbar_rows=128, fuse_planes=False,
                   occupancy=None):
    # fuse_planes is a Pallas MXU-occupancy knob; jnp semantics are
    # plane-order independent so the oracle accepts and ignores it.
    del fuse_planes
    from repro.kernels.ref import psq_matmul_ref

    return psq_matmul_ref(
        x_int, w_int, sf_q, alpha,
        n_a=n_a, n_w=n_w, levels=levels,
        adc_bits=adc_bits, xbar_rows=xbar_rows,
        occupancy=occupancy,
    )


def _reference_int4(x, w_packed, scale):
    from repro.kernels.ref import int4_matmul_ref

    return int4_matmul_ref(w_packed, scale, x)


def _pallas_psq(interpret: bool):
    def call(x_int, w_int, sf_q, alpha, *, n_a, n_w, levels,
             adc_bits=7, xbar_rows=128, fuse_planes=False,
             occupancy=None):
        from repro.kernels.psq_matmul import psq_matmul_kernel

        return psq_matmul_kernel(
            x_int, w_int, sf_q, alpha,
            n_a=n_a, n_w=n_w, levels=levels, adc_bits=adc_bits,
            xbar_rows=xbar_rows, fuse_planes=fuse_planes,
            occupancy=occupancy, interpret=interpret,
        )

    return call


def _pallas_int4(interpret: bool):
    def call(x, w_packed, scale):
        from repro.kernels.int4_matmul import int4_matmul_kernel

        return int4_matmul_kernel(x, w_packed, scale, interpret=interpret)

    return call


def _reference_paged(q, k_pool, v_pool, block_tables, lengths, k_new, v_new):
    from repro.kernels.paged_attention import paged_attention_ref

    return paged_attention_ref(q, k_pool, v_pool, block_tables, lengths,
                               k_new, v_new)


def _pallas_paged(interpret: bool):
    def call(q, k_pool, v_pool, block_tables, lengths, k_new, v_new):
        from repro.kernels.paged_attention import paged_attention_kernel

        return paged_attention_kernel(q, k_pool, v_pool, block_tables,
                                      lengths, k_new, v_new,
                                      interpret=interpret)

    return call


def _compiled_pallas_available() -> bool:
    # the kernels lower through Mosaic (SMEM/VMEM block specs): TPU only;
    # every other platform runs them in interpret mode exclusively.
    return jax.default_backend() == "tpu"


register_backend(KernelBackend(
    name="reference",
    description="pure-jnp bit-exact oracle (conformance baseline)",
    psq_matmul=_reference_psq,
    int4_matmul=_reference_int4,
    paged_attention=_reference_paged,
))

register_backend(KernelBackend(
    name="pallas-interpret",
    description="Pallas kernels, interpreter (portable, correctness path)",
    psq_matmul=_pallas_psq(interpret=True),
    int4_matmul=_pallas_int4(interpret=True),
    paged_attention=_pallas_paged(interpret=True),
))

register_backend(KernelBackend(
    name="pallas",
    description="compiled Pallas kernels (TPU serving fast path)",
    psq_matmul=_pallas_psq(interpret=False),
    int4_matmul=_pallas_int4(interpret=False),
    is_available=_compiled_pallas_available,
    paged_attention=_pallas_paged(interpret=False),
))
