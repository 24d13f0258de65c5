"""Public kernel entry points with backend dispatch.

    mode = "kernel"     pl.pallas_call compiled for TPU (production)
    mode = "interpret"  kernel body executed in Python on CPU (validation)
    mode = "ref"        pure-jnp oracle (CPU tests, the 512-device dry-run —
                        custom calls carry no XLA cost model, DESIGN.md A5)

Default resolves from the REPRO_KERNEL_MODE env var, falling back to "ref"
on CPU hosts and "kernel" when a TPU is present.  On a TPU the mode is
"kernel": ``chip_smoke.py`` refuses to run when the env var overrides it,
since a chip run on the oracles or the interpreter measures nothing real.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional

import jax

from repro.kernels import ref as _ref
from repro.kernels.bank_matmul import bank_matmul as _bank_kernel
from repro.kernels.decode_attention import decode_attention as _decode_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.mamba_scan import mamba_scan as _mamba_kernel
from repro.kernels.page_gather import page_gather as _gather_kernel
from repro.kernels.rg_lru import rg_lru_scan as _rg_lru_kernel


def default_mode() -> str:
    env = os.environ.get("REPRO_KERNEL_MODE")
    if env:
        return env
    return "kernel" if jax.default_backend() == "tpu" else "ref"


# Per-op dispatch counters, incremented at TRACE time (once per compiled
# shape, not once per device launch).  That is exactly the observable the
# dead-kernel gates need: an op whose count stays 0 across a serving run was
# never on any traced hot path — the ssm/griffin bug this table exists to
# keep fixed (benchmarks/mixed_zoo.py asserts mamba_scan/rg_lru_scan > 0).
DISPATCH_COUNTS: dict = {}


def _count(name: str) -> None:
    DISPATCH_COUNTS[name] = DISPATCH_COUNTS.get(name, 0) + 1


def reset_dispatch_counts() -> None:
    DISPATCH_COUNTS.clear()


def dispatch_counts() -> dict:
    """Snapshot of {op_name: trace-time dispatch count} since the last
    reset.  Ops never dispatched are absent (benchmark gates treat missing
    as 0)."""
    return dict(DISPATCH_COUNTS)


def flash_attention(q, k, v, causal=True, window=None, mode: Optional[str] = None,
                    **kw):
    _count("flash_attention")
    mode = mode or default_mode()
    if mode == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_kernel(q, k, v, causal=causal, window=window,
                         interpret=(mode == "interpret"), **kw)


def decode_attention(q, k_cache, v_cache, lengths, mode: Optional[str] = None, **kw):
    _count("decode_attention")
    mode = mode or default_mode()
    if mode == "ref":
        return _ref.decode_attention_ref(q, k_cache, v_cache, lengths)
    return _decode_kernel(q, k_cache, v_cache, lengths,
                          interpret=(mode == "interpret"), **kw)


def rg_lru_scan(a, b, h0, mode: Optional[str] = None, **kw):
    _count("rg_lru_scan")
    mode = mode or default_mode()
    if mode == "ref":
        return _ref.rg_lru_ref(a, b, h0)
    return _rg_lru_kernel(a, b, h0, interpret=(mode == "interpret"), **kw)


def mamba_scan(dt, dtx, Bmat, Cmat, A, h0, mode: Optional[str] = None, **kw):
    _count("mamba_scan")
    mode = mode or default_mode()
    if mode == "ref":
        return _ref.mamba_scan_ref(dt, dtx, Bmat, Cmat, A, h0)
    return _mamba_kernel(dt, dtx, Bmat, Cmat, A, h0,
                         interpret=(mode == "interpret"), **kw)


def page_gather(pool, page_table, mode: Optional[str] = None, **kw):
    _count("page_gather")
    mode = mode or default_mode()
    if mode == "ref":
        return _ref.page_gather_ref(pool, page_table)
    return _gather_kernel(pool, page_table, interpret=(mode == "interpret"), **kw)


def bank_matmul(x, w, b=None, mode: Optional[str] = None, **kw):
    """Grouped GEMM over a leading bank axis: out[n] = x[n] @ w[n] (+ b[n]),
    with x either (N, M, K) banked or (M, K) broadcast — the one-dispatch
    suffix fan-out of a merged serving group (DESIGN.md S2).  The ref oracle
    is an unrolled loop of the per-member contraction, so ref-mode serving
    stays bitwise identical to the per-member path."""
    _count("bank_matmul")
    mode = mode or default_mode()
    if mode == "ref":
        return _ref.bank_matmul_ref(x, w, b)
    return _bank_kernel(x, w, b, interpret=(mode == "interpret"), **kw)


@dataclasses.dataclass(frozen=True)
class OpSpec:
    """One dispatchable op, machine-readable: the contract checker
    (repro.analysis.contracts) proves kernel/ref congruence abstractly over
    this table, and tests/test_kernels.py drives its mode matrix from it —
    adding an op without registering it here fails both."""

    name: str            # public entry-point name in this module
    kernel: object       # Pallas entry point (interpret=bool keyword-only)
    ref: object          # pure-jnp oracle in repro.kernels.ref
    dispatch: object     # the mode-dispatching wrapper above
    array_args: tuple    # positional array params, in call order
    optional_args: tuple = ()  # trailing array params that may be None


OP_TABLE: dict = {
    s.name: s for s in (
        OpSpec("flash_attention", _flash_kernel, _ref.flash_attention_ref,
               flash_attention, ("q", "k", "v")),
        OpSpec("decode_attention", _decode_kernel, _ref.decode_attention_ref,
               decode_attention, ("q", "k_cache", "v_cache", "lengths")),
        OpSpec("rg_lru_scan", _rg_lru_kernel, _ref.rg_lru_ref,
               rg_lru_scan, ("a", "b", "h0")),
        OpSpec("mamba_scan", _mamba_kernel, _ref.mamba_scan_ref,
               mamba_scan, ("dt", "dtx", "Bmat", "Cmat", "A", "h0")),
        OpSpec("page_gather", _gather_kernel, _ref.page_gather_ref,
               page_gather, ("pool", "page_table")),
        OpSpec("bank_matmul", _bank_kernel, _ref.bank_matmul_ref,
               bank_matmul, ("x", "w"), optional_args=("b",)),
    )
}
