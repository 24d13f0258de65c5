"""Mesh-sharded serve tier benchmark (DESIGN.md S3): the LM merged-group
decode scenario served from a ParamStore carrying a ``MeshPlacement`` over a
(devices/4, 4) ``("data", "model")`` mesh built from the devices present,
vs the identical single-device store.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.shard_serve [--json]

Lanes (emitted as ``BENCH_shard``):

1. **bitwise, both modes** — the sharded store replicates trunk buffers
   across the mesh and shards the suffix BANK's leading axis over the
   ``model`` axis (4 shards; the merged (A, B, D, E) group's bank divides
   exactly).  The bank axis is batch-like — no contraction is split — so
   every generated token AND its logits must match the unsharded decoder
   bitwise, in ``ref`` mode and again in ``interpret`` mode (the Pallas
   kernel bodies executing under ``shard_map``).  Chunked prefill is on in
   both lanes, so chunk + shard compose under the same oracle.
2. **per-shard epochs** — ``apply_plan`` on the sharded store advances each
   touched shard's epoch EXACTLY once (one global bump); ``update_buffers``
   on one private key advances exactly that key's home shard.
3. **over-budget admission** — the scheduler budget is set strictly below
   the merged group's total resident bytes (+ activations), i.e. the group
   does NOT fit one device, but at or above the largest per-shard slice —
   sharded admission (replicated trunk per shard, private suffixes on their
   home shards) must serve every request to completion.

It needs a device count that four divides (the bank has four members, one
per ``model`` shard) and exits non-zero otherwise; the CPU lane forces eight
host devices, a v5e host has four chips.
"""
import argparse
import json
import os

import numpy as np

from benchmarks.common import emit

PAGE_SIZE = 4
DECODE_KW = dict(page_size=PAGE_SIZE, num_pages=64, max_slots=8, max_len=16,
                 buckets=(1, 2, 4), record_logits=True, chunked_prefill=True)
PROMPT_LEN = 7
MAX_NEW = 5
N_PER_MODEL = 2
BANK_SHARDS = 4  # the merged (A, B, D, E) group's bank: a member per shard


def serve_rules(mesh):
    """Serve-tier logical rules: every weight buffer REPLICATES (the store's
    residency semantic — each device computes the full trunk), and only the
    suffix bank's leading axis shards (``MeshPlacement.bank_sharding``).
    Replicated weights keep every contraction device-local, which is what
    makes the sharded serve bitwise-verifiable against one device; the
    TP/FSDP weight-sharded alternatives are costed by the roofline's
    collective lane, not served here."""
    from repro.distributed.sharding import LogicalRules

    return LogicalRules(mesh, {})  # unmapped logical axes resolve to None


def _mk_placement():
    import jax

    from repro.distributed.partitioning import MeshPlacement

    n = jax.device_count()
    mesh = jax.make_mesh((n // BANK_SHARDS, BANK_SHARDS), ("data", "model"))
    return MeshPlacement(serve_rules(mesh), bank_axis="model")


def _requests(cfg, mids):
    import jax

    from repro.serving.decode import DecodeRequest

    reqs = []
    for j in range(N_PER_MODEL):
        for i, m in enumerate(mids):
            toks = np.asarray(jax.random.randint(
                jax.random.PRNGKey(500 + 11 * i + j), (PROMPT_LEN,), 0,
                cfg.vocab_size))
            reqs.append(DecodeRequest(m, toks, max_new_tokens=MAX_NEW))
    return reqs


def _engine(adapter, cfg, plan, placement=None, capacity_bytes=10**9):
    from repro.core import ParamStore
    from repro.serving.costs import costs_for
    from repro.serving.executor import MergeAwareEngine, ModelProgram
    from repro.serving.workload import instances_from_store

    from benchmarks.lm_merging import BUCKETS, MIDS, lm_zoo

    store = ParamStore.from_models(lm_zoo(adapter, cfg), placement=placement)
    programs = [ModelProgram.from_adapter(adapter, m, cfg=cfg) for m in MIDS]
    eng = MergeAwareEngine(
        store, instances_from_store(store, "tiny-yolo", model_ids=list(MIDS)),
        programs, capacity_bytes=capacity_bytes,
        costs={"tiny-yolo": costs_for("tiny-yolo")}, buckets=BUCKETS,
    )
    eng.apply_plan(plan)
    return eng


def _completion_map(decoder):
    return {
        (c.request.instance_id, tuple(int(t) for t in c.request.prompt)):
        (list(c.tokens), c.logits)
        for c in decoder.completions
    }


def _bitwise(a: dict, b: dict) -> bool:
    if set(a) != set(b):
        return False
    for k in a:
        if a[k][0] != b[k][0]:
            return False
        for x, y in zip(a[k][1] or [], b[k][1] or []):
            if not np.array_equal(x, y):
                return False
    return True


def _serve_pair(adapter, cfg, plan, placement, mode: str):
    """(unsharded stats+map, sharded stats+map) under one kernel mode.
    ``default_mode`` is read at trace time and JAX reuses a function's
    trace across ``jax.jit`` wrappers, so the switch drops every cached
    trace first; without that, a later mode would replay the earlier mode's
    traces."""
    import jax

    prev = os.environ.get("REPRO_KERNEL_MODE")
    os.environ["REPRO_KERNEL_MODE"] = mode
    jax.clear_caches()
    try:
        base = _engine(adapter, cfg, plan)
        base_stats = base.serve_decode(_requests(cfg, list(base.programs)),
                                       **DECODE_KW)
        base_map = _completion_map(base.last_decoder)
        shard = _engine(adapter, cfg, plan, placement=placement)
        shard_stats = shard.serve_decode(_requests(cfg, list(shard.programs)),
                                         **DECODE_KW)
        shard_map_ = _completion_map(shard.last_decoder)
    finally:
        if prev is None:
            os.environ.pop("REPRO_KERNEL_MODE", None)
        else:
            os.environ["REPRO_KERNEL_MODE"] = prev
        jax.clear_caches()
    return (base_stats, base_map), (shard_stats, shard_map_), shard


def _epoch_accounting(adapter, cfg, plan, placement) -> dict:
    """Per-shard epoch discipline around the two shard-affecting events."""
    from repro.core import ParamStore

    from benchmarks.lm_merging import lm_zoo

    store = ParamStore.from_models(lm_zoo(adapter, cfg), placement=placement)
    before = dict(store.shard_epochs)
    epoch0 = store.epoch
    keys = store.apply_plan(plan)
    bumps = {s: store.shard_epochs.get(s, 0) - before.get(s, 0)
             for s in range(store.n_shards)}
    touched_shards = {store.shard_of(k) for k in keys}
    plan_ok = (store.epoch - epoch0 == 1
               and all(b <= 1 for b in bumps.values())
               and all(bumps[s] == 1 for s in touched_shards))

    # update_buffers on ONE private key: exactly its home shard advances
    priv = next(k for k in sorted(store.buffers) if ":" in k
                and k not in store.shared_keys())
    before = dict(store.shard_epochs)
    store.update_buffers({priv: np.asarray(store.buffers[priv]) * 1.0})
    bumped = [s for s in range(store.n_shards)
              if store.shard_epochs.get(s, 0) != before.get(s, 0)]
    update_ok = bumped == [store.shard_of(priv)]
    return {
        "apply_plan_epoch_bumps": 1 if plan_ok else -1,
        "apply_plan_touched_shards": len(touched_shards),
        "update_buffers_bumped_shards": len(bumped),
        "epoch_bumps_ok": bool(plan_ok and update_ok),
    }


def _over_budget(adapter, cfg, plan, placement) -> dict:
    """Serve the merged group under a budget one device cannot hold."""
    probe = _engine(adapter, cfg, plan, placement=placement)
    store = probe.store
    total = store.resident_bytes()
    by_shard = store.resident_bytes_by_shard()
    act = max(probe.scheduler._activation_bytes(i, 1)
              for i in probe.scheduler.instances.values())
    capacity = max(by_shard.values()) + act + 1
    assert capacity < total + act, "scenario too small to be over budget"
    eng = _engine(adapter, cfg, plan, placement=placement,
                  capacity_bytes=capacity)
    reqs = _requests(cfg, list(eng.programs))
    stats = eng.serve_decode(reqs, **DECODE_KW)
    return {
        "over_budget_capacity_bytes": capacity,
        "over_budget_activation_bytes": act,
        "group_resident_bytes": total,
        "max_shard_resident_bytes": max(by_shard.values()),
        "over_budget_submitted": len(reqs),
        "over_budget_completed": stats["completed"],
        "over_budget_served": (stats["completed"] == len(reqs)
                               and stats["lost_in_flight"] == 0),
        "dma_bytes_by_shard": dict(eng.dma.bytes_by_shard),
    }


def run(quiet: bool = False) -> dict:
    import jax

    from repro.core import MergePlan

    from benchmarks.lm_merging import plan_variants
    from repro.models.registry import get_adapter

    n = jax.device_count()
    if n % BANK_SHARDS:
        raise SystemExit(
            f"shard_serve needs a multiple of {BANK_SHARDS} devices, found "
            f"{n} (on CPU: XLA_FLAGS=--xla_force_host_platform_device_count=8)")

    adapter = get_adapter("dense")
    cfg = adapter.default_config()
    res, _ = plan_variants(adapter, cfg)
    plan = MergePlan.from_json(res.plan.to_json())
    placement = _mk_placement()

    rows = []
    bitwise = {}
    shard_eng = None
    for mode in ("ref", "interpret"):
        (bs, bm), (ss, sm), shard_eng = _serve_pair(
            adapter, cfg, plan, placement, mode)
        bitwise[mode] = _bitwise(bm, sm)
        for lane, st in (("unsharded", bs), ("sharded", ss)):
            rows.append({
                "mode": mode, "lane": lane,
                "completed": st["completed"], "steps": st["steps"],
                "tokens_decoded": st["tokens_decoded"],
                "prefill_chunk_dispatches": st["prefill_chunk_dispatches"],
                "bank_dispatches": st["bank_dispatches"],
                "lost_in_flight": st["lost_in_flight"],
            })

    derived = {
        "sharded": True,
        "devices": jax.device_count(),
        "mesh": "x".join(map(str, placement.mesh.devices.shape)),
        "n_shards": placement.n_shards,
        "bank_sharded_over_model_axis": any(
            shard_eng._bank_sharded) if shard_eng else False,
        "bitwise_ref": bitwise.get("ref", False),
        "bitwise_interpret": bitwise.get("interpret", False),
        **_epoch_accounting(adapter, cfg, plan, placement),
        **_over_budget(adapter, cfg, plan, placement),
    }
    return emit("BENCH_shard", rows, derived, quiet=quiet)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", action="store_true",
                    help="print ONLY the artifact JSON to stdout")
    args = ap.parse_args(argv)
    out = run(quiet=args.json)
    if args.json:
        print(json.dumps(out, indent=2, default=str))
    d = out["derived"]
    checks = (
        d["bitwise_ref"] and d["bitwise_interpret"]
        and d["epoch_bumps_ok"]
        and d["over_budget_served"]
        and d["bank_sharded_over_model_axis"]
    )
    if not checks:
        raise SystemExit("shard_serve acceptance criteria not met")


if __name__ == "__main__":
    main()
