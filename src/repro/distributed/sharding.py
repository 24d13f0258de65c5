"""Logical-axis sharding (MaxText-style rules).

Model code annotates activations with *logical* axis names via
:func:`constrain`; the launcher installs a :class:`LogicalRules` mapping
logical names to mesh axes with :func:`use_rules`.  Outside of a rules
context ``constrain`` is a no-op, so all models run unchanged on a single
CPU device (tests, smoke configs).

Rules used by the production mesh (see launch/mesh.py):

    batch    -> ("pod", "data")     # DP across pods + within pod
    fsdp     -> "data"              # parameter sharding (ZeRO-3 style)
    tensor   -> "model"             # TP: heads / d_ff / vocab / experts
    seq      -> "model"             # context parallelism (qwen3, long ctx)
    expert   -> "model"             # EP for MoE
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axis = Union[str, None, Sequence[str]]

_state = threading.local()


class LogicalRules:
    def __init__(self, mesh: Mesh, rules: dict[str, Axis]):
        self.mesh = mesh
        self.rules = dict(rules)

    def resolve(self, logical_axes: Sequence[Axis]) -> P:
        mesh_axes = []
        used: set[str] = set()
        for ax in logical_axes:
            resolved = self.rules.get(ax) if isinstance(ax, str) else ax
            # Drop mesh axes whose extent doesn't divide — caller guarantees
            # divisibility for the dims that matter; this keeps rules reusable.
            if isinstance(resolved, (list, tuple)):
                resolved = tuple(a for a in resolved if a not in used)
                for a in resolved:
                    used.add(a)
                mesh_axes.append(resolved if resolved else None)
            else:
                if resolved in used:
                    resolved = None
                if resolved is not None:
                    used.add(resolved)
                mesh_axes.append(resolved)
        return P(*mesh_axes)

    def sharding(self, logical_axes: Sequence[Axis]) -> NamedSharding:
        return NamedSharding(self.mesh, self.resolve(logical_axes))


def use_rules(rules: Optional[LogicalRules]):
    @contextlib.contextmanager
    def ctx():
        prev = getattr(_state, "rules", None)
        _state.rules = rules
        try:
            yield rules
        finally:
            _state.rules = prev

    return ctx()


def current_rules() -> Optional[LogicalRules]:
    return getattr(_state, "rules", None)


def _axis_extent(mesh: Mesh, axes) -> int:
    names = axes if isinstance(axes, (list, tuple)) else (axes,)
    extent = 1
    for n in names:
        extent *= mesh.shape[n]
    return extent


def constrain(x: jax.Array, *logical_axes: Axis) -> jax.Array:
    """Annotate ``x`` with logical axes; no-op outside a rules context.
    Mesh axes whose extent does not divide the dim are dropped (replicated)
    so one model definition serves every mesh / batch size."""
    rules = current_rules()
    if rules is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"{len(logical_axes)} axes for rank-{x.ndim} array")
    spec = rules.resolve(logical_axes)
    fixed = []
    for dim, axes in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
        if axes is not None and dim % _axis_extent(rules.mesh, axes) != 0:
            axes = None
        fixed.append(axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rules.mesh, P(*fixed))
    )


def logical_to_spec(rules: Optional[LogicalRules], logical_axes: Sequence[Axis]) -> P:
    if rules is None:
        return P()
    return rules.resolve(logical_axes)


def shard_bank_fn(fn, mesh: Mesh, axis: str):
    """Wrap a bank fan-out callable ``(bank_params, feats) -> (N, ...)`` to
    run shard-locally over the leading bank axis via ``shard_map``: every
    bank leaf splits its member axis over ``axis``, features replicate, and
    the callable traces against the LOCAL member count (N / extent) — so a
    Pallas grouped GEMM's grid and BlockSpecs, and the ref oracle's unrolled
    member loop, both become shard-local without touching the kernel.  The
    bank axis is batch-like (no contraction is split), so the sharded output
    is bitwise identical to the unsharded dispatch (DESIGN.md S3).

    Caller guarantees N divides the axis extent (the divisibility guard in
    ``MeshPlacement.bank_sharding``)."""
    # in_specs are pytree prefixes: P(axis) shards every bank leaf's leading
    # dim; P() replicates the whole feats tree.  check_vma=False: the kernel
    # body (a pallas_call) has no varying-manual-axes rule.
    return jax.shard_map(fn, mesh=mesh, in_specs=(P(axis), P()),
                         out_specs=P(axis), check_vma=False)


def replicate_fn(fn, mesh: Mesh):
    """Wrap ``fn`` to run whole on every device of ``mesh`` via
    ``shard_map``: every operand and result replicated, the body traced
    against full shapes.  A program jitted over a multi-device mesh must
    hand-partition any Pallas TPU (Mosaic) kernel it holds — XLA cannot
    partition the custom call — and the serve tier's replicated trunk is
    exactly this case: each device computes it on its own replica."""
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)
