"""Closed-loop chat (kind ``closed_chat``): clients that each wait for
their reply before sending the next request, decoded greedily through one
``StreamingDecoder`` over the engine's merged zoo.

``bench/traffic.py`` makes each client's requests from the mix.  Before
the window, one pass of short requests takes the decoder through every
bucket its live rows can fill, so each trunk step and bank fan-out shape
is compiled.  Then the loop runs ``warm_steps`` steps, still set-up, so
that when the window opens the rows are at spread-out points of their
requests and none is in its first prompt.  In the window the decoder runs
its own loop (``StreamingDecoder.run``); after every step the clients
whose requests retired send their next one, with no think time.  At the
close, what is in flight finishes in a bounded drain with no new requests.

The decoder holds every client's longest request at once, so each request
starts at the step after its client's last one retired, and the steps at
which requests start follow from their lengths alone (:func:`schedule`);
a request that starts at another step is counted (``late_starts``, held
to 0 by the check).
The seeded sample is drawn from that: per variant, among the requests that
the window's first ``sample_steps`` steps advance and that started after
their client's first (so into a running batch, on pages and a slot that a
retired request freed), the one with the most output tokens and the rest
drawn from the seed.  Their rows alone keep the logits of every token they
emit (the decoder's own per-row record, switched on for them after their
first step, which consumes a prompt token); no other request's logits
outlive its step.
"""
from __future__ import annotations

import gc
import math
import time

import jax
import numpy as np

from bench import costs, harness, model, trace, traffic


def schedule(chats: traffic.ChatClients, steps: int) -> list:
    """``[(client, k, first step, steps live)]`` of every request that the
    loop's first ``steps`` steps advance, the loop starting with every
    client's first request at step 0: a request of prompt S and output N
    is live for S + N - 1 steps, and its client's next starts at the step
    after."""
    out = []
    for c in range(chats.clients):
        t = k = 0
        while t < steps:
            p, o = chats.sizes(c, k)
            out.append((c, k, t, p + o - 1))
            t += p + o - 1
            k += 1
    return out


def sample(mix: dict, seed: int, chats: traffic.ChatClients) -> set:
    """The sampled ``(client, k)`` keys: for each variant, among the
    requests live in the window's first ``sample_steps`` steps that are not
    their client's first (all of the variant's live ones where none is
    later), the one with the most output tokens and the rest drawn from
    the seed."""
    lo = mix["warm_steps"]
    hi = lo + mix["sample_steps"]
    live = [(c, k) for c, k, t, n in schedule(chats, hi) if t + n > lo]
    rng = np.random.default_rng([seed, 2])
    picked = set()
    for v in sorted(set(chats.variant.tolist())):
        mine = [ck for ck in live if chats.variant[ck[0]] == v]
        cands = [ck for ck in mine if ck[1] > 0] or mine
        longest = max(cands, key=lambda ck: (chats.sizes(*ck)[1], ck))
        rest = [ck for ck in cands if ck != longest]
        n = min(mix["sample_per_variant"] - 1, len(rest))
        picked.add(longest)
        picked.update(rest[i] for i in rng.choice(len(rest), n, replace=False))
    return picked


class _Stop:
    """The decoder's clock: the host clock until :attr:`halted`, then
    infinitely late, so ``StreamingDecoder.run`` returns after the step
    that set it."""

    def __init__(self):
        self.halted = False

    def __call__(self) -> float:
        return math.inf if self.halted else time.perf_counter()


class Loop:
    """The clients' side of the loop, called by the decoder after every
    step (``on_step``): resubmits for each retired request while ``open``,
    records each request's submit time, first step and first-token time
    (less ``paused``),
    switches the logits record on for sampled rows, and keeps each step's
    real rows' attended lengths for the work counts."""

    def __init__(self, dec, chats: traffic.ChatClients, mids: tuple,
                 sampled: set):
        self.dec, self.chats, self.mids, self.sampled = dec, chats, mids, sampled
        self.next_k = [0] * chats.clients
        self.submitted: dict = {}  # (client, k) -> host time
        self.first: dict = {}  # (client, k) -> host time of its first token
        self.started: dict = {}  # (client, k) -> index of its first step
        self.done: dict = {}  # (client, k) -> DecodeCompletion
        self.seen = 0  # decoder completions handled
        self.open = True
        self.paused = 0.0  # host seconds the loop stood still, left out of its times
        self.bank_seen = dec.stats["bank_dispatches"]
        self.steps: list = []  # per step: (attended lengths, emitting rows, banked)

    def submit(self, c: int) -> None:
        from repro.serving.decode import DecodeRequest

        k = self.next_k[c]
        self.next_k[c] += 1
        chat = self.chats.request(c, k)
        self.dec.submit(DecodeRequest(self.mids[chat.variant], chat.prompt,
                                      chat.max_new, meta=(c, k)))
        self.submitted[(c, k)] = time.perf_counter() - self.paused

    def on_step(self, dec, step: int) -> None:
        now = time.perf_counter() - self.paused
        lengths, emitting = [], 0
        for s in dec.slots.values():
            key = s.request.meta
            lengths.append(s.length)
            self.started.setdefault(key, len(self.steps))
            if s.pos >= len(s.request.prompt):
                emitting += 1
                self.first.setdefault(key, now)
            if key in self.sampled and s.logits is None and not s.out_tokens:
                s.logits = []
        new, self.seen = dec.completions[self.seen:], len(dec.completions)
        for c in new:
            key = c.request.meta
            lengths.append(len(c.request.prompt) + len(c.tokens) - 1)
            emitting += 1
            self.first.setdefault(key, now)
            self.done[key] = c
            if self.open:
                self.submit(key[0])
        banked = dec.stats["bank_dispatches"] - self.bank_seen
        self.bank_seen = dec.stats["bank_dispatches"]
        self.steps.append((lengths, emitting, banked))


def _warm(dec, mids: tuple) -> None:
    """Compile every shape the window reaches: ``max_slots`` two-token
    prompts asking for 1, 2, ... tokens, so the live rows step down
    through every bucket, each a trunk step and a bank fan-out over the
    merged group."""
    from repro.serving.decode import DecodeRequest

    n = dec.max_slots
    if n + 1 > dec.max_len:
        raise ValueError(f"max_len {dec.max_len} cannot warm {n} slots")
    reqs = [DecodeRequest(mids[i % len(mids)], np.zeros(2, np.int32), i + 1)
            for i in range(n)]
    out = dec.run(reqs, horizon_s=harness.DRAIN_S, warmup=False)
    if out["completed"] != n:
        raise RuntimeError(f"warm-up finished {out['completed']} of {n}")
    dec.completions.clear()


def _counters(dec, before: dict) -> dict:
    keys = ("steps", "tokens_decoded", "prompt_tokens", "trunk_dispatches",
            "bank_dispatches", "head_dispatches", "admitted", "retired")
    return {k: dec.stats[k] - before.get(k, 0) for k in keys}


def step_work(m: model.Model, steps: list, buckets: tuple,
              page_size: int) -> tuple:
    """``(work, useful_flops)`` of decode steps, each ``(attended lengths
    of its real rows, rows that emitted a token, bank dispatches)``: the
    trunk's kernels at the rows' true lengths, the bank's over the step's
    bucket, and the useful FLOPs of the real rows (trunk at the true
    context, and one head for each row that emitted a token)."""
    from repro.serving.workload import bucket_for

    fam = model.family(m.family)
    work: dict = {}
    useful = 0
    for lengths, emitting, banked in steps:
        for kernel, fb in fam.decode_work(m, lengths, page_size).items():
            costs.add(work, kernel, fb)
        if banked:
            costs.add(work, "bank_matmul", costs.bank_matmul(
                m.variants, bucket_for(len(lengths), buckets), m.d_model,
                m.vocab), banked)
        useful += (sum(fam.token_flops(m, n) for n in lengths)
                   + emitting * costs.head_token_flops(m))
    return work, useful


def drive(z: harness.Zoo, mix: dict, seed: int, seconds: float,
          clock: harness.Clock, tracer: harness.Tracer) -> harness.Window:
    from repro.serving.decode import StreamingDecoder

    m = z.m
    knobs = mix["decoder"]
    stop = _Stop()
    dec = StreamingDecoder(z.engine, page_size=knobs["page_size"],
                           num_pages=knobs["num_pages"],
                           max_slots=knobs["max_slots"], max_len=knobs["max_len"],
                           buckets=tuple(knobs["buckets"]), clock=stop)
    _warm(dec, z.mids)
    chats = traffic.ChatClients(mix, seed, m.variants, m.vocab)
    pages = -(-chats.longest // dec.page_size)
    if chats.clients > dec.max_slots or chats.clients * pages > dec.num_pages:
        raise ValueError(f"{chats.clients} clients of up to {pages} pages each "
                         f"over {dec.max_slots} slots and {dec.num_pages} pages")
    loop = Loop(dec, chats, z.mids, sample(mix, seed, chats))
    pool = dec.pool_for(z.mids[0])

    def warm_step(d, step: int) -> None:
        loop.on_step(d, step)
        stop.halted = len(loop.steps) >= mix["warm_steps"]

    for c in range(chats.clients):
        loop.submit(c)
    dec.run([], horizon_s=harness.DRAIN_S, on_step=warm_step, warmup=False)
    if not stop.halted:
        raise RuntimeError(f"the loop made {len(loop.steps)} of its "
                           f"{mix['warm_steps']} warm steps in {harness.DRAIN_S} s")
    stop.halted = False
    open_at_start = [k for k in loop.submitted if k not in loop.done]
    first_step = len(loop.steps)
    before = dict(dec.stats)

    gc.collect()
    gc.freeze()
    tracer.start()
    t_win = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        dec.run([], horizon_s=seconds, on_step=loop.on_step, warmup=False)
    t_end = time.perf_counter()
    tracer.stop()
    # the profiler's stop (tens of seconds) is not the program's: a request
    # that waits through it for its first token is not charged for it
    loop.paused = time.perf_counter() - t_end
    loop.open = False
    counters = _counters(dec, before)
    window = loop.steps[first_step:]
    submitted = [k for k, t in loop.submitted.items() if t >= t_win]
    peak = harness._peak_bytes()
    drained = dec.run([], horizon_s=harness.DRAIN_S, on_step=loop.on_step,
                      warmup=False)
    t_drained = time.perf_counter()
    gc.unfreeze()

    window_s = t_end - t_win
    work, useful = step_work(m, window, dec.buckets, dec.page_size)
    answers, missing = [], 0
    for key in sorted(loop.sampled):
        c = loop.done.get(key)
        if c is None or c.logits is None or len(c.logits) != len(c.tokens):
            missing += 1
            continue
        prompt = np.asarray(c.request.prompt, np.int32)
        answers.append(harness.Answer(
            int(chats.variant[key[0]]),
            np.concatenate([prompt, np.asarray(c.tokens[:-1], np.int32)]),
            np.stack(c.logits), np.asarray(c.tokens)))
    ttft = [loop.first[k] - loop.submitted[k] for k in submitted if k in loop.first]
    rows = [len(lengths) for lengths, _, _ in window]
    attempted = open_at_start + submitted
    planned = {(c, k): t for c, k, t, _ in schedule(chats, len(loop.steps))}
    log = {
        "decode.sampled": sorted(loop.sampled),
        "decode.steps_rows_mean": [len(window), float(np.mean(rows)) if rows else 0.0],
        "decode.completed_in_window": counters["retired"],
        "decode.ttft_ms.p50_p95_max": [
            1e3 * float(np.median(ttft)), 1e3 * harness._p95(ttft),
            1e3 * max(ttft)] if ttft else [],
        "decode.drain_s_steps": [t_drained - t_end - loop.paused,
                                 drained["steps"] - counters["steps"] - before["steps"]],
    }
    return harness.Window(
        t_window=t_win, window_s=window_s, peak_bytes=peak,
        attempted=len(attempted),
        failed=sum(1 for k in attempted if k not in loop.done),
        end_to_end={"decode_tok_s": (counters["tokens_decoded"] / window_s,
                                     "tokens/s")},
        counters=counters, work=work, useful_flops=useful, answers=answers,
        readings={"ttft_s": ttft, "rows_per_step": rows,
                  "pool_bytes": pool.k.nbytes + pool.v.nbytes},
        log=log,
        exact={"lost_in_flight": drained["lost_in_flight"],
               "pool_faults": int(not drained["pool_identity_ok"]),
               "sample_missing": missing,
               "late_starts": sum(planned.get(k) != t
                                  for k, t in loop.started.items())})
