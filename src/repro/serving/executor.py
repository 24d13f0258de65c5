"""Real (non-simulated) edge executors: jitted forwards for the models in a
ParamStore, driving the same Scheduler policy objects as the simulator.

Two serve paths share the policy layer:

* :class:`EdgeExecutor` — the straightforward per-request loop (one forward
  per request, synchronous DMA).  Kept as the baseline the benchmarks compare
  against.
* :class:`MergeAwareEngine` — the merge-aware hot path (DESIGN.md S1):
  cached materialisation (``ParamStore.materialize_cached``), shared-prefix
  batched execution (one stem run per micro-batch for models whose prefix
  weights are bound to the same store keys), suffix-bank fan-out (DESIGN.md
  S2: congruent private heads stacked into one leading-axis weight bank and
  executed in ONE dispatch per micro-batch), deadline-sorted micro-batches,
  async DMA prefetch (the next group's incremental load overlaps the
  current group's compute instead of stalling the accelerator), and hot
  MergePlan swap (``apply_plan``: a cloud-shipped plan lands on the live
  engine with one epoch bump and no dropped requests — DESIGN.md P1) plus
  the symmetric drift ``revert`` (a breached model drops back to its
  original private weights under load, queued requests surviving, driven by
  ``serving/lifecycle.py`` — DESIGN.md L1).

The DMA delay is modelled (the host has no PCIe-attached accelerator) but
residency, eviction and merging-aware incremental loads are all real key-set
operations.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.store import ParamStore
from repro.serving.scheduler import Instance, Scheduler
from repro.serving.workload import bucket_for, deadline_microbatches, pad_stack


def base_model_id(instance_id: str) -> str:
    """ParamStore bindings key for an instance id: feed instances are named
    ``<model>#<k>`` (``workload.build_instances``); bare model ids pass
    through unchanged."""
    return instance_id.split("#", 1)[0]


@dataclasses.dataclass
class Request:
    instance_id: str
    payload: Any
    arrival_s: float
    deadline_s: float
    meta: Any = None  # opaque caller tag (e.g. (camera, frame_index))


class PlanApplyError(RuntimeError):
    """A hot plan swap failed mid-flight.  The engine guarantees the store
    was rolled back to its pre-swap buffers/bindings with exactly ONE epoch
    bump and no queued request dropped; callers (LifecycleController) keep
    serving the prior plan."""


def drop_expired(queues: dict, now: float) -> int:
    """Drop queue heads whose deadline has passed; returns the count.  The
    ONE expiry helper both executors share — expired requests are counted
    (``dropped_expired``), never silently vanished, so shed-rate accounting
    in the ingestion monitors stays honest."""
    n = 0
    for q in queues.values():
        while q and now > q[0].deadline_s:
            q.popleft()
            n += 1
    return n


@dataclasses.dataclass
class Completion:
    request: Request
    result: Any
    finished_s: float

    @property
    def met_sla(self) -> bool:
        return self.finished_s <= self.request.deadline_s


class EdgeExecutor:
    """instances + forward fns + store -> serve loop over a request queue."""

    def __init__(
        self,
        store: ParamStore,
        instances: list,
        forward_fns: dict,  # instance_id -> callable(params, payload)
        capacity_bytes: int,
        costs: dict,
        dma_gbps: float = 16.0,
        simulate_dma: bool = True,
        idle_sleep_s: float = 2e-4,
        buckets: tuple = (1, 2, 4, 8),
        clock: Callable[[], float] = time.monotonic,
    ):
        self.store = store
        self.clock = clock  # injected so harness replays can freeze time
        self.scheduler = Scheduler(instances, capacity_bytes, costs)
        self.forward = {
            iid: jax.jit(fn) for iid, fn in forward_fns.items()
        }
        self.dma_gbps = dma_gbps
        self.simulate_dma = simulate_dma
        self.idle_sleep_s = idle_sleep_s
        self.buckets = tuple(sorted(buckets))
        self.queues = {i.instance_id: deque() for i in instances}
        self.completions: list = []
        self.skipped: int = 0
        self.dropped_expired: int = 0

    def submit(self, req: Request):
        self.queues[req.instance_id].append(req)

    def _drop_expired(self, now: float):
        n = drop_expired(self.queues, now)
        self.skipped += n
        self.dropped_expired += n

    def serve(self, horizon_s: float, batch: int = 1, warmup: Any = None,
              drain: bool = False) -> dict:
        """Round-robin over instances until the horizon (or, with
        ``drain=True``, until every queue is empty); returns stats.
        ``warmup`` payload (optional) compiles each instance's forward before
        the SLA clock starts — deployments always pre-compile.

        The requests taken from a queue run as ONE padded batch through the
        same :func:`pad_stack` bucket ladder the engine uses (a bounded set
        of jit shapes), so the baseline is honest about batching — what it
        lacks vs the engine is sharing, prefetch and the suffix bank, not
        the ability to stack frames."""
        order = [i.instance_id for i in self.scheduler.order]
        ladder = tuple(sorted({b for b in self.buckets if b <= batch} | {batch}))
        if warmup is not None:
            for iid in order:
                params = self.store.materialize_cached(base_model_id(iid))
                for b in ladder:
                    wb, _ = pad_stack([warmup] * b, b)
                    jax.block_until_ready(self.forward[iid](params, wb))
        t0 = self.clock()
        idx = 0
        empty_streak = 0
        while self.clock() - t0 < horizon_s:
            iid = order[idx % len(order)]
            idx += 1
            now = self.clock() - t0
            self._drop_expired(now)
            q = self.queues[iid]
            if not q:
                if drain and not any(self.queues.values()):
                    break
                empty_streak += 1
                if empty_streak >= len(order):
                    # every queue was empty for a full pass: yield instead of
                    # busy-spinning on the monotonic clock
                    time.sleep(self.idle_sleep_s)
                    empty_streak = 0
                continue
            empty_streak = 0
            r = self.scheduler.load(iid, batch)
            if self.simulate_dma and r["loaded_bytes"]:
                time.sleep(r["loaded_bytes"] / 1e9 / self.dma_gbps)
            params = self.store.materialize_cached(base_model_id(iid))
            taken = [q.popleft() for _ in range(min(batch, len(q)))]
            stacked, _ = pad_stack([req.payload for req in taken],
                                   bucket_for(len(taken), ladder))
            out = self.forward[iid](params, stacked)
            jax.block_until_ready(out)
            done = self.clock() - t0
            for j, req in enumerate(taken):
                self.completions.append(Completion(req, out[j], done))
        met = sum(1 for c in self.completions if c.met_sla)
        total = len(self.completions) + self.skipped
        return {
            "completed": len(self.completions),
            "met_sla": met,
            "skipped": self.skipped,
            "dropped_expired": self.dropped_expired,
            "sla_fraction": met / max(total, 1),
        }

    def serve_decode(self, requests: list, programs: list, max_len: int = 64,
                     horizon_s: float = 60.0, warmup: bool = True) -> dict:
        """Per-request decode baseline lane (DESIGN.md D1): each request gets
        its own contiguous KV cache (``DecodeSplit.init_cache``) and runs
        sequential ``step_unpaged`` calls to completion, one request at a
        time in EDF order — chunked prompt ingestion (ONE step over the whole
        prompt, so the denominator isn't a token-by-token strawman) followed
        by one single-token step per generated token.  Greedy argmax over
        the full padded vocab, same as the streaming engine.  Stats mirror
        the engine's ``tokens_decoded`` / ``steps`` / ``prompt_tokens`` so
        ``benchmarks/decode_serve.py`` compares like for like."""
        from repro.serving.decode import DecodeCompletion

        progs = {p.instance_id: p for p in programs}
        for req in requests:
            if progs[req.instance_id].decode is None:
                raise ValueError(f"{req.instance_id}: program has no decode "
                                 "surface (adapter lacks can_decode)")
        jitted: dict = {}

        def step_fn(dec):
            fn = jitted.get(id(dec.step_unpaged))
            if fn is None:
                fn = jitted[id(dec.step_unpaged)] = jax.jit(dec.step_unpaged)
            return fn

        import numpy as np

        order = sorted(requests, key=lambda r: (r.deadline_s, r.arrival_s))
        if warmup:  # pre-compile both shapes (prompt chunk + single token)
            seen = set()
            for req in order:
                dec = progs[req.instance_id].decode
                key = (id(dec), len(req.prompt))
                if key in seen:
                    continue
                seen.add(key)
                params = self.store.materialize_cached(
                    base_model_id(req.instance_id))
                step = step_fn(dec)
                cache = dec.init_cache(1, max_len)
                chunk = jnp.zeros((1, len(req.prompt)), jnp.int32)
                _, cache = step(params, cache, chunk)
                lg, _ = step(params, cache, jnp.zeros((1, 1), jnp.int32))
                jax.block_until_ready(lg)

        stats = {"steps": 0, "tokens_decoded": 0, "prompt_tokens": 0}
        completions: list = []
        t0 = self.clock()
        for req in order:
            if self.clock() - t0 > horizon_s:
                break
            iid = req.instance_id
            dec = progs[iid].decode
            r = self.scheduler.load(iid, 1)
            if self.simulate_dma and r["loaded_bytes"]:
                time.sleep(r["loaded_bytes"] / 1e9 / self.dma_gbps)
            params = self.store.materialize_cached(base_model_id(iid))
            step = step_fn(dec)
            cache = dec.init_cache(1, max_len)
            prompt = jnp.asarray(req.prompt, jnp.int32)[None, :]
            logits, cache = step(params, cache, prompt)
            stats["steps"] += 1
            stats["prompt_tokens"] += len(req.prompt)
            out = [int(np.argmax(np.asarray(logits)[0, -1]))]
            stats["tokens_decoded"] += 1
            for _ in range(req.max_new_tokens - 1):
                tok = jnp.full((1, 1), out[-1], jnp.int32)
                logits, cache = step(params, cache, tok)
                stats["steps"] += 1
                out.append(int(np.argmax(np.asarray(logits)[0, 0])))
                stats["tokens_decoded"] += 1
            completions.append(
                DecodeCompletion(req, out, self.clock() - t0))
        self.decode_completions = completions
        elapsed = self.clock() - t0
        return {
            "completed": len(completions),
            "elapsed_s": elapsed,
            "tokens_per_s": stats["tokens_decoded"] / max(elapsed, 1e-9),
            **stats,
        }


# ---------------------------------------------------------------------------
# Merge-aware engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ModelProgram:
    """How the engine runs one instance.  ``forward`` is the whole model;
    when ``prefix``/``suffix`` are given the model is split so the engine can
    execute a merged stem once per micro-batch and fan out only the private
    head.  ``prefix_paths`` are the flat param paths the prefix reads — the
    engine checks against ``ParamStore.binding_signature`` that every path is
    bound to the same store key across candidate group members before it ever
    shares a prefix run.

    The suffix-bank tier (DESIGN.md S2): ``suffix_paths``/``suffix_signature``
    describe the private head's stacked-weight congruence and ``bank_suffix``
    (optional) is the adapter's fused fan-out ``(bank_params, feats) ->
    (N, B, ...)``.  Group members whose suffix signatures all match execute
    every private head in ONE dispatch instead of one per member."""

    instance_id: str
    model_id: str  # ParamStore bindings key
    forward: Callable  # (params, batched_x) -> batched_out
    prefix: Optional[Callable] = None  # (params, batched_x) -> batched_feats
    suffix: Optional[Callable] = None  # (params, batched_feats) -> batched_out
    prefix_paths: Optional[frozenset] = None
    suffix_paths: Optional[frozenset] = None
    suffix_signature: Optional[tuple] = None
    bank_suffix: Optional[Callable] = None  # (bank_params, feats) -> (N, ...)
    decode: Optional[Any] = None  # registry.DecodeSplit — streaming lane (D1)

    @classmethod
    def from_adapter(cls, adapter, instance_id: str,
                     model_id: Optional[str] = None, cfg=None,
                     split: bool = True) -> "ModelProgram":
        """Build a program from a registered ``MergeableAdapter`` — the one
        way models meet the engine (DESIGN.md P3); no more hand-wired
        closures per call site.  The adapter caches the cfg-bound forward
        and prefix/suffix callables, so every instance of one (adapter, cfg)
        hands the engine the SAME function objects and a shared-prefix group
        compiles once (see ``MergeAwareEngine._prefix_fn``)."""
        cfg = adapter.default_config() if cfg is None else cfg
        fwd = adapter.bound_forward(cfg)
        sp = adapter.split(cfg) if (split and adapter.can_split) else None
        ds = (adapter.decode_split(cfg)
              if (split and getattr(adapter, "can_decode", False)) else None)
        return cls(
            instance_id, model_id if model_id is not None else instance_id,
            forward=fwd,
            prefix=sp.prefix if sp else None,
            suffix=sp.suffix if sp else None,
            prefix_paths=sp.prefix_paths if sp else None,
            suffix_paths=sp.suffix_paths if sp else None,
            suffix_signature=sp.suffix_signature if sp else None,
            bank_suffix=sp.bank_suffix if sp else None,
            decode=ds,
        )


class AsyncDMA:
    """Models an async host->device copy engine: ``start`` begins a transfer
    (wall-clock timestamped), ``wait`` blocks only for the portion that did
    not overlap the compute issued in between.  With ``simulate=False`` the
    bookkeeping still runs (stall/hidden stats) but nothing sleeps — the path
    a real DMA queue would take."""

    def __init__(self, gbps: float, simulate: bool = True,
                 clock: Callable[[], float] = time.monotonic):
        self.gbps = gbps
        self.simulate = simulate
        self.clock = clock
        self._inflight: dict = {}  # key -> (t_start, duration_s)
        self.stall_s = 0.0
        self.hidden_s = 0.0
        self.transfers = 0
        # per-shard transferred-bytes ledger (DESIGN.md S3): the sharded
        # engine attributes each load's bytes to the shards they land on
        self.bytes_by_shard: dict = {}

    def seconds_for(self, nbytes: int) -> float:
        return nbytes / 1e9 / self.gbps

    def account(self, shard_bytes: dict) -> None:
        """Credit a completed load's bytes to the shards they landed on
        (``Scheduler.load``'s ``loaded_bytes_by_shard``)."""
        for s, b in shard_bytes.items():
            if b:
                self.bytes_by_shard[s] = self.bytes_by_shard.get(s, 0) + b

    def start(self, key, nbytes: int) -> None:
        self._inflight[key] = (self.clock(), self.seconds_for(nbytes))
        if nbytes:
            self.transfers += 1

    def wait(self, key, nbytes: int) -> float:
        """Block until the transfer for ``key`` is done; returns the visible
        stall.  A key never started (cold miss) pays the full transfer."""
        entry = self._inflight.pop(key, None)
        now = self.clock()
        if entry is None:
            remaining = self.seconds_for(nbytes)
            if nbytes:
                self.transfers += 1
        else:
            t_start, dur = entry
            elapsed = now - t_start
            remaining = Scheduler.overlapped_load_ms(dur * 1e3, elapsed * 1e3) / 1e3
            self.hidden_s += min(dur, elapsed)
        self.stall_s += remaining
        if self.simulate and remaining > 0:
            time.sleep(remaining)
        return remaining


class MergeAwareEngine:
    """Batched, prefetching serve loop over a merged ParamStore.

    Execution plan (recomputed whenever the store's binding epoch moves):
    instances whose ``prefix_paths`` all bind to identical store keys form a
    *shared-prefix group* — their stems are one physical set of weights, so
    one prefix run serves every member's requests in a micro-batch; private
    suffixes fan out per instance.  Groups are visited in the scheduler's
    merging-aware round-robin order and the next group's incremental load is
    prefetched during the current group's compute.
    """

    def __init__(
        self,
        store: ParamStore,
        instances: list,
        programs: list,
        capacity_bytes: int,
        costs: dict,
        dma_gbps: float = 16.0,
        simulate_dma: bool = True,
        buckets: tuple = (1, 2, 4, 8),
        idle_sleep_s: float = 2e-4,
        suffix_bank: bool = True,
        clock: Callable[[], float] = time.monotonic,
    ):
        self.store = store
        self.clock = clock  # shared with the DMA model below
        # with a mesh-sharded store the capacity budget is PER-SHARD and
        # admission checks every shard's slice (replicated trunk everywhere,
        # private suffixes on their home shard) — DESIGN.md S3
        self.scheduler = Scheduler(
            instances, capacity_bytes, costs,
            shard_fn=(store.resident_shards if store.n_shards > 1 else None),
            n_shards=store.n_shards,
        )
        self.programs = {p.instance_id: p for p in programs}
        missing = set(self.programs) ^ {i.instance_id for i in instances}
        if missing:
            raise ValueError(f"programs/instances mismatch: {missing}")
        self._replicated: dict = {}  # (callable, mesh) -> shard_map'd fn
        self._fwd = {p.instance_id: jax.jit(self.maybe_replicate(p.forward))
                     for p in programs}
        # prefixes compile lazily, cached per (callable identity, binding
        # signature): instances whose prefix weights are one physical buffer
        # set share ONE jitted prefix instead of tracing per instance
        self._prefix_compiled: dict = {}
        self._suffix = {p.instance_id: (jax.jit(self.maybe_replicate(p.suffix))
                                        if p.suffix else None)
                        for p in programs}
        self.dma = AsyncDMA(dma_gbps, simulate=simulate_dma, clock=clock)
        self.buckets = tuple(sorted(buckets))
        self.idle_sleep_s = idle_sleep_s
        self.suffix_bank = suffix_bank
        self.queues = {i.instance_id: deque() for i in instances}
        self.completions: list = []
        self.skipped = 0
        self.stats = {
            "prefix_runs": 0, "suffix_runs": 0, "forward_runs": 0,
            "microbatches": 0, "param_lookups": 0, "idle_sleeps": 0,
            "prefix_jits": 0, "suffix_dispatches": 0, "bank_hits": 0,
            "dropped_expired": 0,
        }
        self._groups: list = []
        self._groups_epoch = -1
        self._sigs: dict = {}  # iid -> binding signature, per groups epoch
        self._bankable: dict = {}  # group tuple -> bool, per groups epoch
        self._bank_compiled: dict = {}  # (callable, sig, N) -> jitted bank fn
        self._bank_sharded: dict = {}  # (callable, N, mesh, axis) -> shard_map'd fn

    # -- prefix compile cache (one trace per shared-prefix group) --------------

    @staticmethod
    def _callable_key(fn):
        """Trace-sharing identity of a prefix callable: closures produced
        from one body over the same captured values (e.g. per-instance
        lambdas from a list comprehension, or an adapter's cached split)
        compare equal, so a 4-member shared-prefix group maps onto ONE
        jitted prefix.  Falls back to object identity when the closure or
        defaults are unhashable."""
        code = getattr(fn, "__code__", None)
        if code is None:
            return id(fn)
        try:
            cells = tuple(id(c.cell_contents) for c in (fn.__closure__ or ()))
            key = (code, fn.__defaults__, cells)
            hash(key)
            return key
        except (TypeError, ValueError):
            return id(fn)

    def _binding_sig(self, iid: str) -> tuple:
        p = self.programs[iid]
        sig = self._sigs.get(iid)
        if sig is None:
            sig = self.store.binding_signature(p.model_id, p.prefix_paths)
            self._sigs[iid] = sig
        return sig

    def _prefix_fn(self, iid: str):
        """Jitted prefix for ``iid``.  Keyed by (callable, binding
        signature): group members bound to identical prefix keys reuse the
        same compiled entry — ``prefix_jits`` in the stats counts distinct
        compilations, so a 4-member group reports 1, not 4."""
        p = self.programs[iid]
        key = (self._callable_key(p.prefix), self._binding_sig(iid))
        fn = self._prefix_compiled.get(key)
        if fn is None:
            fn = jax.jit(self.maybe_replicate(p.prefix))
            self._prefix_compiled[key] = fn
            self.stats["prefix_jits"] += 1
        return fn

    # -- suffix bank (DESIGN.md S2) -------------------------------------------

    def _group_bankable(self, group: tuple) -> bool:
        """A shared group's fan-out runs as ONE banked dispatch iff every
        member's private head is congruent: same suffix paths and the same
        suffix signature (the adapter's shape/dtype fingerprint over the
        suffix leaves).  Cached per binding-epoch plan — an unmerge or plan
        swap re-evaluates eligibility on the next pass."""
        hit = self._bankable.get(group)
        if hit is None:
            progs = [self.programs[i] for i in group]
            sigs = {p.suffix_signature for p in progs}
            paths = {p.suffix_paths for p in progs}
            hit = (self.suffix_bank and len(group) > 1
                   and None not in sigs and len(sigs) == 1
                   and None not in paths and len(paths) == 1)
            self._bankable[group] = hit
        return hit

    def _bank_sharding_active(self, n_bank: int) -> bool:
        """Sharded bank dispatch is on iff the store carries a mesh placement
        with >1 shards on the bank axis AND the bank divides evenly over
        them (indivisible banks fall back to the replicated local dispatch —
        still bitwise, just not scaled)."""
        pl = self.store.placement
        return (pl is not None and self.store.n_shards > 1
                and n_bank % self.store.n_shards == 0)

    def maybe_shard_bank(self, fn, n_bank: int):
        """Wrap a bank fan-out callable ``(bank_params, feats) -> (N, ...)``
        in a ``shard_map`` over the placement's bank axis when sharding is
        active for ``n_bank`` (DESIGN.md S3): each device runs the SAME
        computation over its N/n_shards bank slice with replicated
        activations — the bank axis is batch-like, no contraction is split,
        so outputs stay bitwise identical to the unsharded dispatch while
        the grid (and Pallas BlockSpecs) become shard-local.  Cached per
        (callable, N, mesh, axis) so repeat callers (and the streaming
        decoder's jit cache) see a stable function identity.  A bank the
        shards do not divide runs replicated (:meth:`maybe_replicate`)."""
        if not self._bank_sharding_active(n_bank):
            return self.maybe_replicate(fn)
        from repro.distributed.sharding import shard_bank_fn

        pl = self.store.placement
        key = (self._callable_key(fn), n_bank, pl.mesh, pl.bank_axis)
        wrapped = self._bank_sharded.get(key)
        if wrapped is None:
            wrapped = shard_bank_fn(fn, pl.mesh, pl.bank_axis)
            self._bank_sharded[key] = wrapped
        return wrapped

    def maybe_replicate(self, fn):
        """``fn`` itself on one device; under a multi-device mesh placement,
        ``fn`` shard_map'd with every operand replicated, so each device
        runs it whole on its replica (``sharding.replicate_fn``).  Pallas
        TPU kernels cannot sit in an auto-partitioned multi-device program,
        so every jitted serve callable goes through here.  Cached per
        (callable, mesh) for a stable function identity."""
        pl = self.store.placement
        if pl is None or pl.mesh.size == 1:
            return fn
        from repro.distributed.sharding import replicate_fn

        key = (self._callable_key(fn), pl.mesh)
        wrapped = self._replicated.get(key)
        if wrapped is None:
            wrapped = self._replicated[key] = replicate_fn(fn, pl.mesh)
        return wrapped

    def _bank_fn(self, group: list):
        """Jitted bank fan-out for a group: the adapter's fused
        ``bank_suffix`` when provided (``ops.bank_matmul`` grouped GEMM on
        TPU; the unrolled bitwise oracle in ``ref`` mode), else ``vmap`` of
        the member suffix over the stacked bank — the fallback for suffixes
        with no bank-aware callable (allclose-grade, still one dispatch).
        Under an active mesh placement the callable is shard_map'd over the
        bank axis first (:meth:`maybe_shard_bank`), so the dispatch scales
        with devices at unchanged output bits."""
        lead = self.programs[group[0]]
        sharded = self._bank_sharding_active(len(group))
        mesh = self.store.placement.mesh if sharded else None
        if lead.bank_suffix is not None:
            key = (self._callable_key(lead.bank_suffix),
                   lead.suffix_signature, len(group), mesh)
            base = lead.bank_suffix
        else:
            key = (self._callable_key(lead.suffix), "vmap",
                   lead.suffix_signature, len(group), mesh)
            base = None
        fn = self._bank_compiled.get(key)
        if fn is None:
            base_fn = (base if base is not None
                       else jax.vmap(lead.suffix, in_axes=(0, None)))
            fn = jax.jit(self.maybe_shard_bank(base_fn, len(group)))
            self._bank_compiled[key] = fn
        return fn

    def _bank_params(self, group: list):
        """Stacked suffix-bank pytree for the group, via the store's
        epoch-cached bank materialisation; ``bank_hits`` counts cache-served
        dispatches (one rebuild per group per binding epoch otherwise)."""
        self.stats["param_lookups"] += 1
        mids = tuple(self.programs[i].model_id for i in group)
        bid = ParamStore.bank_id(mids)
        before = self.store.materializations.get(bid, 0)
        tree = self.store.materialize_bank(
            mids, self.programs[group[0]].suffix_paths)
        if self.store.materializations.get(bid, 0) == before:
            self.stats["bank_hits"] += 1
        return tree

    # -- plan -----------------------------------------------------------------

    def prefix_groups(self) -> list:
        """Shared-prefix groups as lists of instance ids, ordered by first
        appearance in the merging-aware round-robin order.  Cached per store
        binding epoch: an unmerge splits a group on the next serve pass."""
        if self._groups_epoch == self.store.epoch:
            return self._groups
        self._sigs = {}  # epoch moved: binding signatures may have changed
        self._bankable = {}  # and group membership (bank eligibility) with them
        groups: list = []
        by_sig: dict = {}
        for inst in self.scheduler.order:
            iid = inst.instance_id
            p = self.programs[iid]
            if not (p.prefix and p.suffix and p.prefix_paths):
                groups.append([iid])
                continue
            sig = self._binding_sig(iid)
            if sig in by_sig:
                by_sig[sig].append(iid)
            else:
                by_sig[sig] = member = [iid]
                groups.append(member)
        # evict compiled prefixes whose binding signature died with the old
        # epoch — a long-lived engine replanning repeatedly must not pin
        # every historical jitted wrapper (and its executables) forever
        self._prefix_compiled = {
            k: v for k, v in self._prefix_compiled.items() if k[1] in by_sig
        }
        self._groups = groups
        self._groups_epoch = self.store.epoch
        return groups

    # -- hot plan swap / revert ------------------------------------------------

    def rebind_instances(self, key_bytes_fn=None) -> dict:
        """Rebuild scheduler instances from the store's CURRENT bindings
        (cost id and accuracy carried over per instance) and swap them in
        via ``Scheduler.rebind``, which preserves residency for surviving
        keys — the shared tail of ``apply_plan`` (P1 hot swap) and
        ``revert`` (L1 drift revert)."""
        from repro.utils.tree import leaf_bytes

        old = self.scheduler.instances
        kb_by_model: dict = {}  # store model -> {key: bytes}, computed once
        insts = []
        for iid, inst in old.items():
            mid = self.programs[iid].model_id
            if mid not in kb_by_model:
                kb_by_model[mid] = {
                    k: (key_bytes_fn(k, leaf_bytes(self.store.buffers[k]))
                        if key_bytes_fn else leaf_bytes(self.store.buffers[k]))
                    for k in self.store.keys_for(mid)
                }
            kb = kb_by_model[mid]
            insts.append(Instance(iid, inst.model_id, frozenset(kb), kb,
                                  inst.accuracy))
        return self.scheduler.rebind(insts)

    def apply_plan(self, plan, key_bytes_fn=None) -> dict:
        """Apply a MergePlan on the LIVE engine (DESIGN.md P1 hot swap):

        1. ``ParamStore.apply_plan`` stages every column rebind and commits
           with a *single* epoch bump — the prefix-group plan and every
           cached pytree invalidate exactly once;
        2. scheduler instances are rebuilt from the store's post-plan
           bindings (cost id and accuracy carried over per instance) and
           swapped in via ``Scheduler.rebind``, which preserves residency
           for keys the plan kept;
        3. queues are untouched — in-flight requests are served against the
           new bindings on the next pass (the serve loop re-reads
           ``prefix_groups()`` every iteration).

        The swap is ATOMIC under failure: ``ParamStore.apply_plan`` mutates
        buffers/bindings column by column and bumps the epoch only at the
        end, so an exception mid-flight (a poisoned payload, an injected
        fault) would otherwise strand a half-rebound store at the OLD epoch
        — every epoch-keyed cache would happily serve stale pytrees over
        partially mutated bindings.  The engine snapshots buffers + bindings
        up front; on any failure it restores both wholesale, settles the
        epoch at exactly ONE bump past the pre-swap value (consumers
        invalidate once, same as a successful swap), rebinds the scheduler
        from the restored bindings, and re-raises :class:`PlanApplyError`.
        Queues are never touched, so no queued request is dropped by a
        failed swap.
        """
        epoch0 = self.store.epoch
        buffers0 = dict(self.store.buffers)
        bindings0 = {m: dict(b) for m, b in self.store.bindings.items()}
        try:
            shared = self.store.apply_plan(plan)
        except Exception as exc:
            self.store.buffers.clear()
            self.store.buffers.update(buffers0)
            self.store.bindings.clear()
            self.store.bindings.update(bindings0)
            if self.store.epoch == epoch0:
                self.store.bump_epoch()  # one bump total for the failed swap
            else:
                self.store._cache.clear()  # already bumped: just invalidate
            self.rebind_instances(key_bytes_fn)
            raise PlanApplyError(f"plan swap failed and was rolled back: "
                                 f"{exc}") from exc
        rebind = self.rebind_instances(key_bytes_fn)
        return {
            "shared_keys": shared,
            "epoch_bumps": self.store.epoch - epoch0,
            "pending_requests": sum(len(q) for q in self.queues.values()),
            **rebind,
        }

    def revert(self, monitor, report, key_bytes_fn=None) -> dict:
        """Revert breached models to their original weights on the LIVE
        engine (§5.1 step 5, DESIGN.md L1) — the drift-side twin of
        ``apply_plan``, with the same no-drain guarantees:

        1. ``DriftMonitor.revert`` stages every breached model's private
           rebind and commits with a *single* epoch bump — cached pytrees,
           the prefix-group plan AND the suffix-bank materialisations all
           invalidate exactly once (``materialize_bank`` caches live in the
           same store cache ``bump_epoch`` clears);
        2. scheduler instances are rebuilt from the post-revert bindings;
           shared keys still referenced by surviving group members stay
           resident (``Scheduler.rebind``), so survivors' next loads are
           still free — only the reverted model pays its private bytes;
        3. queues are untouched: requests queued at breach time are served
           against the reverted bindings on the next pass, never dropped.
        """
        epoch0 = self.store.epoch
        pending = sum(len(q) for q in self.queues.values())
        monitor.revert(report)
        rebind = self.rebind_instances(key_bytes_fn)
        return {
            "reverted": sorted(report.reverted),
            "epoch_bumps": self.store.epoch - epoch0,
            "pending_requests": pending,
            **rebind,
        }

    # -- queue plumbing --------------------------------------------------------

    def submit(self, req: Request):
        self.queues[req.instance_id].append(req)

    def _drop_expired(self, now: float):
        n = drop_expired(self.queues, now)
        self.skipped += n
        self.stats["dropped_expired"] += n

    def _params(self, iid: str):
        self.stats["param_lookups"] += 1
        return self.store.materialize_cached(self.programs[iid].model_id)

    # -- execution -------------------------------------------------------------

    def _run_group(self, group: list, reqs: list, t0: float):
        """One group visit: deadline-sorted micro-batches over the union of
        the group's drained requests; shared groups run the prefix once per
        batch, singletons run the whole forward batched.

        Congruent shared groups additionally run the *suffix bank* stage
        (DESIGN.md S2): every member's private head executes over the whole
        micro-batch in ONE dispatch against the stacked bank weights — no
        per-member row gathers, no per-member suffix launches — and each
        completion scatters out of the (member, row) cell of the bank
        output.  The bank runs ALL of the group's heads, so it pays off
        exactly when a micro-batch fans out: batches whose rows belong to a
        single member keep the per-member path (one dispatch either way, no
        wasted head FLOPs under skewed traffic).  ``suffix_dispatches``
        counts device dispatches for suffix work (1 per banked micro-batch
        vs one per member otherwise); ``suffix_runs`` keeps counting
        logical member-head executions."""
        mbs = deadline_microbatches(reqs, self.buckets)
        shared = len(group) > 1
        bankable = shared and self._group_bankable(tuple(group))
        for mb in mbs:
            self.stats["microbatches"] += 1
            batch, n = pad_stack([r.payload for r in mb.requests], mb.bucket)
            banked = bankable and len(
                {r.instance_id for r in mb.requests}) > 1
            if banked:
                lead = group[0]
                feats = self._prefix_fn(lead)(self._params(lead), batch)
                self.stats["prefix_runs"] += 1
                bank_out = self._bank_fn(group)(self._bank_params(group), feats)
                self.stats["suffix_runs"] += len(group)
                self.stats["suffix_dispatches"] += 1
                jax.block_until_ready(bank_out)
                slot = {iid: i for i, iid in enumerate(group)}
                done = self.clock() - t0
                for j, r in enumerate(mb.requests):
                    self.completions.append(
                        Completion(r, bank_out[slot[r.instance_id], j], done))
                continue
            rows_by_iid: dict = {}
            for j, r in enumerate(mb.requests):
                rows_by_iid.setdefault(r.instance_id, []).append(j)
            if shared:
                lead = group[0]
                feats = self._prefix_fn(lead)(self._params(lead), batch)
                self.stats["prefix_runs"] += 1
                outs, pos = {}, {}
                for iid, idx in rows_by_iid.items():
                    if len(idx) == mb.bucket:
                        sub = feats  # whole batch belongs to this instance
                    else:
                        # fan out only this instance's rows, padded back onto
                        # the bucket ladder so suffix shapes stay bounded
                        sb = next(b for b in self.buckets if len(idx) <= b)
                        take = idx + [idx[-1]] * (sb - len(idx))
                        sub = feats[jnp.asarray(take)]
                    outs[iid] = self._suffix[iid](self._params(iid), sub)
                    pos[iid] = {g: k for k, g in enumerate(idx)}
                    self.stats["suffix_runs"] += 1
                    self.stats["suffix_dispatches"] += 1
            else:
                (iid,) = group
                outs = {iid: self._fwd[iid](self._params(iid), batch)}
                pos = {iid: {j: j for j in range(len(mb.requests))}}
                self.stats["forward_runs"] += 1
            for o in outs.values():
                jax.block_until_ready(o)
            done = self.clock() - t0
            for j, r in enumerate(mb.requests):
                row = pos[r.instance_id][j]
                self.completions.append(Completion(r, outs[r.instance_id][row], done))

    def _warmup(self, payload) -> None:
        """Pre-compile every (group, bucket) shape before the SLA clock
        starts — deployments always pre-compile.  ``payload`` follows the
        request-payload contract (a single frame, optionally with a leading
        batch-1 axis) and goes through the same :func:`pad_stack` as the
        serve path, so exactly the serving shapes are compiled."""
        for group in self.prefix_groups():
            banked = len(group) > 1 and self._group_bankable(tuple(group))
            for b in self.buckets:
                batch, _ = pad_stack([payload] * b, b)
                if len(group) > 1:
                    feats = self._prefix_fn(group[0])(self._params(group[0]), batch)
                    if banked:
                        # single-member micro-batches still take the
                        # per-member path, so compile both fan-outs
                        jax.block_until_ready(
                            self._bank_fn(group)(self._bank_params(group), feats))
                    for iid in group:
                        jax.block_until_ready(
                            self._suffix[iid](self._params(iid), feats))
                else:
                    (iid,) = group
                    jax.block_until_ready(self._fwd[iid](self._params(iid), batch))

    def serve_decode(self, requests: list, horizon_s: float = 60.0,
                     on_step: Optional[Callable] = None, **kw) -> dict:
        """Streaming decode lane (DESIGN.md D1): paged KV pool + continuous
        batching via ``serving.decode.StreamingDecoder`` — the shared trunk
        of a merged group advances every in-flight row ONE token per step in
        a single dispatch, private heads fan out through the suffix bank.
        ``**kw`` forwards pool/batching knobs (``page_size``, ``num_pages``,
        ``max_slots``, ``max_len``, ``record_logits``); ``on_step(decoder,
        step)`` fires after every engine step (the mid-decode hot-swap hook).
        The decoder is kept on ``last_decoder`` for verification
        (completions, pool accounting, recorded logits)."""
        from repro.serving.decode import StreamingDecoder

        dec = StreamingDecoder(self, **kw)
        self.last_decoder = dec
        return dec.run(requests, horizon_s=horizon_s, on_step=on_step)

    def serve(self, horizon_s: float, warmup: Any = None, drain: bool = True) -> dict:
        """Serve until the horizon (or until the queues are drained, with
        ``drain=True``).  Returns stats including cache/prefetch health."""
        if warmup is not None:
            self._warmup(warmup)
        # per-call accounting: every counter below is reported as the delta
        # over this serve() call (the instance-level counters keep cumulating)
        mat_before = dict(self.store.materializations)
        stats_before = dict(self.stats)
        done_before = len(self.completions)
        skipped_before = self.skipped
        stall_before, hidden_before = self.dma.stall_s, self.dma.hidden_s
        epoch_start = self.store.epoch
        t0 = self.clock()
        gi = 0
        empty_streak = 0
        while self.clock() - t0 < horizon_s:
            groups = self.prefix_groups()  # re-plan if an epoch moved
            now = self.clock() - t0
            self._drop_expired(now)
            if not any(self.queues.values()):
                if drain:
                    break
                self.stats["idle_sleeps"] += 1
                time.sleep(self.idle_sleep_s)
                continue
            group = groups[gi % len(groups)]
            nxt = groups[(gi + 1) % len(groups)]
            gi += 1
            reqs = []
            for iid in group:
                q = self.queues[iid]
                while q:
                    reqs.append(q.popleft())
            if not reqs:
                empty_streak += 1
                if empty_streak >= len(groups):
                    self.stats["idle_sleeps"] += 1
                    time.sleep(self.idle_sleep_s)
                    empty_streak = 0
                continue
            empty_streak = 0
            max_batch = min(len(reqs), self.buckets[-1])
            loaded = 0
            shard_bytes: dict = {}
            for iid in group:
                r = self.scheduler.load(iid, max_batch)
                loaded += r["loaded_bytes"]
                for s, b in r["loaded_bytes_by_shard"].items():
                    shard_bytes[s] = shard_bytes.get(s, 0) + b
            self.dma.wait(tuple(group), loaded)
            self.dma.account(shard_bytes)
            # prefetch the NEXT group's incremental bytes; the transfer's
            # clock runs while this group computes (§3.2 pipelining, made
            # real).  Sized by peek (pre-eviction estimate).
            if tuple(nxt) != tuple(group):
                pre = sum(self.scheduler.peek_load_bytes(iid) for iid in nxt)
                self.dma.start(tuple(nxt), pre)
            self._run_group(group, reqs, t0)
        new = self.completions[done_before:]
        met = sum(1 for c in new if c.met_sla)
        skipped = self.skipped - skipped_before
        total = len(new) + skipped
        lookups = self.stats["param_lookups"] - stats_before["param_lookups"]
        rebuilds = sum(self.store.materializations.get(m, 0) - mat_before.get(m, 0)
                       for m in self.store.materializations)
        last = max((c.finished_s for c in new), default=0.0)
        return {
            "completed": len(new),
            "met_sla": met,
            "skipped": skipped,
            "sla_fraction": met / max(total, 1),
            "elapsed_s": last,
            "requests_per_s": len(new) / max(last, 1e-9),
            "cache_hit_rate": 1.0 - rebuilds / max(lookups, 1),
            "materializations": rebuilds,
            "binding_epochs": self.store.epoch - epoch_start + 1,
            "dma_stall_s": self.dma.stall_s - stall_before,
            "dma_hidden_s": self.dma.hidden_s - hidden_before,
            "dma_bytes_by_shard": dict(self.dma.bytes_by_shard),
            # lifetime count (compiles usually happen in warmup, so the
            # per-call delta under-reports): distinct compiled prefixes —
            # a 4-member shared group contributes 1, not 4
            "prefix_jits_total": self.stats["prefix_jits"],
            **{k: v - stats_before[k] for k, v in self.stats.items()},
        }
