"""Open-loop streams (kind ``open_streams``): cameras that send documents at
fixed rates, each scored by the variants the mix deals it, through
``MergeAwareEngine.serve``.

``bench/traffic.py`` makes the schedule from the mix.  A generator thread
submits each request at its due time while the serving thread runs one
``serve`` call over the window; latency runs from the due time, so a stall
charges every request it delays.  The seeded sample's served logits stay
on the device until the window has closed.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import threading
import time
from collections import defaultdict

import jax
import numpy as np

from bench import costs, harness, model, trace, traffic


class _Sink(list):
    """Stands in for ``engine.completions``: records each completion's time
    on the run's clock and the micro-batch it rode in, and drops every
    device result but the sampled ones, so the window holds no other
    served logits on the chip.

    The sample is ``sample`` (drawn from the seed) and one request from the
    first micro-batch whose rows all belong to one variant, among those
    holding a request numbered ``single_from`` or later: such batches take
    the per-member head, the others the suffix bank.  A micro-batch's
    completions arrive together, one after another with one time, so each
    batch is judged when the next begins.  The sampled results stay on the
    device until :meth:`results`, after the window."""

    def __init__(self, clock: harness.Clock, sample: set, single_from: int):
        super().__init__()
        self.clock, self.sample, self.single_from = clock, sample, single_from
        self.done: dict = {}  # request index -> completion time
        self.batches: dict = defaultdict(list)  # completion time -> [instance]
        self.held: dict = {}  # sampled request index -> device result
        self.single = None  # index sampled from a single-variant micro-batch
        self._open: list = []  # (time, index, instance, result) of one batch

    def append(self, c) -> None:
        t = self.clock.mark + c.finished_s
        if self._open and self._open[0][0] != t:
            self._close()
        i = c.request.meta
        self.done[i] = t
        self.batches[t].append(c.request.instance_id)
        self._open.append((t, i, c.request.instance_id, c.result))
        super().append(dataclasses.replace(c, result=None))

    def _close(self) -> None:
        batch, self._open = self._open, []
        for _, i, _, res in batch:
            if i in self.sample:
                self.held[i] = res
        if self.single is None and len({iid for _, _, iid, _ in batch}) == 1:
            late = [(i, res) for _, i, _, res in batch if i >= self.single_from]
            if late:
                i, res = late[0]
                self.single, self.held[i] = i, res

    def results(self) -> dict:
        """The sampled results, copied to the host."""
        self._close()
        return {i: np.asarray(r) for i, r in self.held.items()}


def _warm(engine, mids, payload, buckets) -> None:
    """Compile every shape the open loop can reach: each bucket's trunk,
    bank and per-member head (``serve(warmup=...)``), then one drained
    micro-batch of every size, single-variant and mixed, for the eager
    gathers and slices around them."""
    from repro.serving.executor import Request

    engine.serve(horizon_s=0.0, warmup=payload, drain=True)
    for n in range(1, buckets[-1] + 1):
        for mixed in (False, True) if n > 1 else (False,):
            for j in range(n):
                engine.submit(Request(mids[j % len(mids)] if mixed else mids[0],
                                      payload, 0.0, 1e9))
            engine.serve(horizon_s=1e9, drain=True)
    engine.completions.clear()


def sample(mix: dict, seed: int, items: list, seconds: float) -> tuple:
    """The seeded sample of a window's requests: ``sample_per_variant`` of
    each variant, and the index from which the single-variant pick starts,
    all among the requests due in the window's first half, so every sampled
    answer is held on the device well before the window's close."""
    rng = np.random.default_rng([seed, 2])
    first = [it for it in items if it.due_s < seconds / 2] or items
    by_var = defaultdict(list)
    for it in first:
        by_var[it.variant].append(it.index)
    picked = set(traffic.sample_indices(rng, by_var, mix["sample_per_variant"]))
    single_from = int(rng.integers(0, max(1, len(first) // 2)))
    return picked, single_from


def batch_work(m: model.Model, batches: list, buckets: tuple, seq: int,
               banked: bool) -> tuple:
    """``(work, useful_flops, mixed)`` of the window's micro-batches, each
    the list of its rows' instances: the trunk's kernels at the batch's
    bucket, the suffix bank's over the bucket's positions for every batch
    that mixes variants, and the useful FLOPs of the real rows (trunk and
    one head per row)."""
    from repro.serving.workload import bucket_for

    fam = model.family(m.family)
    # a row scored causally: its i-th token attends i positions
    row_flops = (sum(fam.token_flops(m, i) for i in range(1, seq + 1))
                 + seq * costs.head_token_flops(m))
    work: dict = {}
    useful = mixed = 0
    for members in batches:
        n = len(members)
        b = bucket_for(n, buckets)
        useful += n * row_flops
        for kernel, fb in fam.score_work(m, b, seq).items():
            costs.add(work, kernel, fb)
        if len(set(members)) > 1:
            mixed += 1
            if banked:
                costs.add(work, "bank_matmul", costs.bank_matmul(
                    m.variants, b * seq, m.d_model, m.vocab))
    return work, useful, mixed


def drive(z: harness.Zoo, mix: dict, seed: int, seconds: float,
          clock: harness.Clock, tracer: harness.Tracer) -> harness.Window:
    from repro.serving.executor import Request

    eng, m = z.engine, z.m
    items = traffic.open_schedule(mix, seed, seconds, m.variants, m.vocab)
    picked, single_from = sample(mix, seed, items, seconds)
    reqs = [Request(z.mids[it.variant], it.tokens[None, :], it.due_s,
                    it.due_s + mix["deadline_s"], meta=it.index) for it in items]
    eng.completions = []
    _warm(eng, z.mids, reqs[0].payload, eng.buckets)
    sink = _Sink(clock, picked, single_from)
    eng.completions = sink
    late = []

    def generate(t0):
        for r in reqs:
            wait = t0 + r.arrival_s - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            late.append(time.perf_counter() - t0 - r.arrival_s)
            eng.submit(r)

    # what set-up left behind is never collected again in the window: a
    # full collection then walks only the window's own objects
    gc.collect()
    gc.freeze()
    tracer.start()
    t_win = time.perf_counter()
    gen = threading.Thread(target=generate, args=(t_win,), daemon=True)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        gen.start()
        clock.arm()
        stats = eng.serve(horizon_s=seconds, drain=False)
        gen.join()
    t_end = time.perf_counter()
    window_batches = list(sink.batches.values())
    peak = harness._peak_bytes()
    clock.arm()
    eng.serve(horizon_s=harness.DRAIN_S, drain=True)
    # the device metrics are clipped to the window's span; stopping the
    # profiler (which writes its file) after the drain keeps that wait out
    # of the latency of the requests the window left open
    tracer.stop()
    gc.unfreeze()
    results = sink.results()

    lat = [(sink.done[it.index] - t_win - it.due_s) if it.index in sink.done
           else math.inf for it in items]
    S = mix["prompt_tokens"]
    work, useful, mixed = batch_work(m, window_batches, eng.buckets, S, z.banked)
    counters = {k: stats[k] for k in ("completed", "microbatches",
                                      "suffix_dispatches", "met_sla")}
    window_s = t_end - t_win
    log = {"mixed_batches": mixed, "single_variant_sampled": sink.single}
    finite = sorted(x for x in lat if math.isfinite(x))
    if finite:
        log["latency_ms.p50_p90_p99_max"] = [
            1e3 * finite[min(len(finite) - 1, int(q * len(finite)))]
            for q in (0.5, 0.9, 0.99, 1.0)]
    worst = int(np.argmax(late)) if late else 0
    log["generator_late_ms.p95_max_at_s"] = [
        1e3 * harness._p95(late) if late else 0, 1e3 * max(late, default=0),
        items[worst].due_s if late else 0]
    return harness.Window(
        t_window=t_win, window_s=window_s, peak_bytes=peak,
        attempted=len(items), failed=sum(1 for x in lat if math.isinf(x)),
        end_to_end={"score_docs_s": (counters["completed"] / window_s, "docs/s")},
        counters=counters, work=work, useful_flops=useful,
        answers=[harness.Answer(items[i].variant, items[i].tokens, got)
                 for i, got in sorted(results.items())],
        readings={"latency_s": lat, "mixed_batches": mixed},
        log=log)
