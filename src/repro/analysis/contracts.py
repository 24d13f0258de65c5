"""Abstract kernel-contract verification (DESIGN.md A7/K-series).

``jax.eval_shape`` traces a function with :class:`jax.ShapeDtypeStruct`
stand-ins — no device, no data, milliseconds per case — and Pallas kernels
declare ``out_shape``, so the whole dispatch surface of
:data:`repro.kernels.ops.OP_TABLE` can be proven *structurally* correct on
any CPU-only CI runner:

* **completeness** — every op in the table has contract cases and vice
  versa; the table's entries really are the module's public dispatchers.
* **signature congruence** — kernel, ref oracle and dispatcher agree on the
  array-argument names and order; every kernel entry point takes
  ``interpret`` keyword-only with no default (the A102 invariant, checked
  here a second time at the object level rather than the AST level).
* **shape/dtype congruence** — for each case in a swept grid, and for each
  of f32 and bf16 inputs, the ref oracle and every requested dispatch mode
  produce identical output trees.  The expectations encode the accumulation
  contract: scans and the suffix-bank GEMM surface f32 outputs even from
  bf16 inputs, attention returns the query dtype (f32 accumulation stays
  internal), page_gather preserves the pool dtype.
* **guards** — shape combinations that violate a kernel's asserts (head
  grouping, contraction dims, scan block divisibility) must RAISE at trace
  time, not miscompute.  Attention and the bank GEMM pad a ragged sequence
  or row count instead; those shapes are ordinary cases.

``run_contracts`` takes the table/cases/modes as injectable arguments so the
unit tests can feed it a deliberately skewed fake op and watch it fail.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import os
from typing import Callable, Optional

import jax
import jax.numpy as jnp


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _sig_tree(x):
    """A comparable (shape, dtype-name) tree of an eval_shape result."""
    return jax.tree_util.tree_map(
        lambda l: (tuple(l.shape), jnp.dtype(l.dtype).name), x)


@dataclasses.dataclass(frozen=True)
class Case:
    """One point of an op's contract grid.  ``arrays(dtype)`` builds the
    name -> ShapeDtypeStruct call kwargs; ``expect(dtype)`` the output tree
    the contract promises; ``static`` rides along as plain kwargs."""

    label: str
    arrays: Callable
    expect: Callable
    static: dict = dataclasses.field(default_factory=dict)
    dtypes: tuple = ("float32", "bfloat16")


@dataclasses.dataclass(frozen=True)
class GuardCase:
    """A shape/static combination the kernel must REJECT (raise at trace
    time) rather than miscompute."""

    label: str
    arrays: Callable
    static: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass(frozen=True)
class OpContract:
    cases: tuple
    guards: tuple = ()


def build_contracts() -> dict:
    """The contract grid for every op in kernels/ops.py."""
    i32 = jnp.int32

    def flash(B, S, Hq, Hkv, D):
        return lambda dt: dict(q=_sds((B, S, Hq, D), dt),
                               k=_sds((B, S, Hkv, D), dt),
                               v=_sds((B, S, Hkv, D), dt))

    def decode(B, Smax, Hq, Hkv, D):
        return lambda dt: dict(q=_sds((B, Hq, D), dt),
                               k_cache=_sds((B, Smax, Hkv, D), dt),
                               v_cache=_sds((B, Smax, Hkv, D), dt),
                               lengths=_sds((B,), i32))

    return {
        "flash_attention": OpContract(
            cases=(
                Case("gqa_causal", flash(2, 16, 4, 2, 8),
                     lambda dt: _sds((2, 16, 4, 8), dt)),
                Case("mha_windowed", flash(1, 32, 2, 2, 16),
                     lambda dt: _sds((1, 32, 2, 16), dt),
                     static=dict(causal=True, window=8)),
                # a block not dividing S: the wrapper pads S and slices back
                Case("ragged_seq_padded", flash(2, 16, 4, 2, 8),
                     lambda dt: _sds((2, 16, 4, 8), dt),
                     static=dict(block_q=12)),
            ),
            guards=(
                GuardCase("kv_heads_not_dividing_q_heads",
                          flash(2, 16, 4, 3, 8)),
            ),
        ),
        "decode_attention": OpContract(
            cases=(
                Case("gqa_cache", decode(2, 32, 4, 2, 8),
                     lambda dt: _sds((2, 4, 8), dt)),
                Case("mha_cache", decode(3, 64, 2, 2, 16),
                     lambda dt: _sds((3, 2, 16), dt)),
                # a block not dividing Smax: the wrapper pads, lengths mask
                Case("ragged_cache_padded", decode(2, 32, 4, 2, 8),
                     lambda dt: _sds((2, 4, 8), dt),
                     static=dict(block_k=12)),
            ),
            guards=(
                GuardCase("kv_heads_not_dividing_q_heads",
                          decode(2, 32, 4, 3, 8)),
            ),
        ),
        "rg_lru_scan": OpContract(
            cases=(
                Case("diag_recurrence",
                     lambda dt: dict(a=_sds((2, 8, 16), dt),
                                     b=_sds((2, 8, 16), dt),
                                     h0=_sds((2, 16), dt)),
                     # f32 accumulation is part of the contract: the carry
                     # surfaces at f32 regardless of the input dtype
                     lambda dt: (_sds((2, 8, 16), jnp.float32),
                                 _sds((2, 16), jnp.float32))),
            ),
            guards=(
                GuardCase("block_d_not_dividing_d",
                          lambda dt: dict(a=_sds((2, 8, 16), dt),
                                          b=_sds((2, 8, 16), dt),
                                          h0=_sds((2, 16), dt)),
                          static=dict(block_d=12)),
            ),
        ),
        "mamba_scan": OpContract(
            cases=(
                Case("selective_scan",
                     lambda dt: dict(dt=_sds((2, 8, 16), dt),
                                     dtx=_sds((2, 8, 16), dt),
                                     Bmat=_sds((2, 8, 4), dt),
                                     Cmat=_sds((2, 8, 4), dt),
                                     A=_sds((16, 4), dt),
                                     h0=_sds((2, 16, 4), dt)),
                     lambda dt: (_sds((2, 8, 16), jnp.float32),
                                 _sds((2, 16, 4), jnp.float32))),
            ),
            guards=(
                GuardCase("chunk_not_dividing_S",
                          lambda dt: dict(dt=_sds((2, 8, 16), dt),
                                          dtx=_sds((2, 8, 16), dt),
                                          Bmat=_sds((2, 8, 4), dt),
                                          Cmat=_sds((2, 8, 4), dt),
                                          A=_sds((16, 4), dt),
                                          h0=_sds((2, 16, 4), dt)),
                          static=dict(chunk=3)),
            ),
        ),
        "page_gather": OpContract(
            cases=(
                Case("paged_assembly",
                     lambda dt: dict(pool=_sds((8, 32), dt),
                                     page_table=_sds((4,), i32)),
                     lambda dt: _sds((4, 32), dt)),
            ),
        ),
        "bank_matmul": OpContract(
            cases=(
                Case("banked_with_bias",
                     lambda dt: dict(x=_sds((3, 16, 8), dt),
                                     w=_sds((3, 8, 16), dt),
                                     b=_sds((3, 16), dt)),
                     lambda dt: _sds((3, 16, 16), jnp.float32)),
                Case("broadcast_no_bias",
                     lambda dt: dict(x=_sds((16, 8), dt),
                                     w=_sds((3, 8, 16), dt)),
                     lambda dt: _sds((3, 16, 16), jnp.float32)),
                # a block not dividing M = batch * seq: rows padded, sliced
                Case("ragged_rows_padded",
                     lambda dt: dict(x=_sds((3, 16, 8), dt),
                                     w=_sds((3, 8, 16), dt)),
                     lambda dt: _sds((3, 16, 16), jnp.float32),
                     static=dict(block_m=12)),
            ),
            guards=(
                GuardCase("contraction_mismatch",
                          lambda dt: dict(x=_sds((3, 16, 9), dt),
                                          w=_sds((3, 8, 16), dt))),
            ),
        ),
    }


# ---------------------------------------------------------------------------
# Checks
# ---------------------------------------------------------------------------


def _positional_names(fn):
    sig = inspect.signature(fn)
    return [p.name for p in sig.parameters.values()
            if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD)]


def _check_signatures(spec, fail):
    arrays = list(spec.array_args) + list(spec.optional_args)
    for role, fn in (("kernel", spec.kernel), ("ref", spec.ref),
                     ("dispatch", spec.dispatch)):
        try:
            sig = inspect.signature(fn)
        except (TypeError, ValueError):
            fail(f"{spec.name}: {role} has no inspectable signature")
            continue
        pos = _positional_names(fn)
        if pos[:len(arrays)] != arrays:
            fail(f"{spec.name}: {role} positional args {pos[:len(arrays)]} "
                 f"!= declared array args {arrays}")
        if role == "kernel":
            p = sig.parameters.get("interpret")
            if p is None or p.kind is not p.KEYWORD_ONLY:
                fail(f"{spec.name}: kernel `interpret` must be keyword-only")
            elif p.default is not p.empty:
                fail(f"{spec.name}: kernel `interpret` must have no default")
        if role == "dispatch" and "mode" not in sig.parameters:
            fail(f"{spec.name}: dispatch takes no `mode` argument")


def _check_case(spec, case, modes, fail):
    for dtype in case.dtypes:
        dt = jnp.dtype(dtype)
        kwargs = case.arrays(dt)
        want = _sig_tree(case.expect(dt))
        # the oracle defines the semantics; it must itself honor the contract
        targets = [("ref", functools.partial(spec.ref, **case_ref_statics(
            spec, case)))]
        targets += [(f"mode={m}",
                     functools.partial(spec.dispatch, mode=m, **case.static))
                    for m in modes]
        for label, fn in targets:
            try:
                got = _sig_tree(jax.eval_shape(fn, **kwargs))
            except Exception as e:  # noqa: BLE001 — report, don't crash CI
                fail(f"{spec.name}:{case.label}:{dt.name}:{label}: "
                     f"eval_shape raised {type(e).__name__}: {e}")
                continue
            if got != want:
                fail(f"{spec.name}:{case.label}:{dt.name}:{label}: "
                     f"output {got} != contract {want}")


def case_ref_statics(spec, case) -> dict:
    """The subset of a case's statics the ref oracle understands (block
    sizes and chunking are kernel-only tuning knobs)."""
    params = inspect.signature(spec.ref).parameters
    return {k: v for k, v in case.static.items() if k in params}


def _check_guard(spec, guard, fail):
    kwargs = guard.arrays(jnp.dtype("float32"))
    fn = functools.partial(spec.dispatch, mode="interpret", **guard.static)
    try:
        jax.eval_shape(fn, **kwargs)
    except Exception:  # the guard fired at trace time — contract holds
        return
    fail(f"{spec.name}:guard:{guard.label}: expected the kernel to reject "
         "this shape/config, but eval_shape succeeded")


def run_contracts(table: Optional[dict] = None,
                  cases: Optional[dict] = None,
                  modes: Optional[tuple] = None) -> dict:
    """Verify every op contract; returns a JSON-able report dict with a
    ``failures`` list (empty == all contracts hold)."""
    from repro.kernels import ops

    bound_table = table is None
    table = ops.OP_TABLE if table is None else table
    cases = build_contracts() if cases is None else cases
    if modes is None:
        env = os.environ.get("REPRO_KERNEL_MODE")
        modes = (env,) if env else ("ref", "interpret")

    failures: list = []
    checks = 0
    per_op: dict = {}

    def fail(msg):
        failures.append(msg)

    missing = sorted(set(table) - set(cases))
    extra = sorted(set(cases) - set(table))
    if missing:
        fail(f"ops without contract cases: {', '.join(missing)}")
    if extra:
        fail(f"contract cases without a table entry: {', '.join(extra)}")

    for name, spec in sorted(table.items()):
        before = len(failures)
        if spec.name != name:
            fail(f"{name}: table key != OpSpec.name {spec.name!r}")
        if bound_table and getattr(ops, name, None) is not spec.dispatch:
            fail(f"{name}: OP_TABLE dispatch is not the module's "
                 "public entry point")
        _check_signatures(spec, fail)
        contract = cases.get(name)
        n_cases = 0
        if contract is not None:
            for case in contract.cases:
                _check_case(spec, case, modes, fail)
                n_cases += 1
                checks += len(case.dtypes) * (1 + len(modes))
            for guard in contract.guards:
                _check_guard(spec, guard, fail)
                checks += 1
        per_op[name] = {"cases": n_cases,
                        "ok": len(failures) == before}
    return {"modes": list(modes), "ops": per_op, "checks": checks,
            "failures": failures, "ok": not failures}
