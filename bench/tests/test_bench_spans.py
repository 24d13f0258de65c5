"""Device idle time charged to the engine's spans (bench/spans.py): the
arithmetic on traces built by hand, and the spans the program records in a
CPU profiler trace of a tiny engine."""
import types

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import harness, roofline, spans, trace
from bench.tests import tiny
from bench.trace import Event, Trace

MS = 1_000_000  # ns
READERS = {stage: f"engine.idle_{stage}.score" for stage in spans.STAGES}


def readers() -> dict:
    """The four readers, as BENCHMARK.json resolves them for the cell."""
    metrics = harness.resolve(harness.load_spec(), tiny.CELL).metrics
    return {stage: metrics[name] for stage, name in READERS.items()}


def microbatch(t, pad=2, dispatch=3, wait=10, complete=4, gap=0):
    """An ``engine.microbatch`` from ``t`` (ms) and its four stages, with
    ``gap`` ms of the bare micro-batch before ``engine.complete``."""
    ev, cur = [], t
    for name, d in (("pad", pad), ("dispatch", dispatch), ("wait", wait)):
        ev.append(Event(f"engine.{name}", cur * MS, d * MS))
        cur += d
    cur += gap
    ev.append(Event("engine.complete", cur * MS, complete * MS))
    cur += complete
    return [Event("engine.microbatch", t * MS, (cur - t) * MS)] + ev


def hand_trace(device, host, window=(0, 100)):
    return Trace([device] if device is not None else [],
                 [Event(trace.WINDOW, window[0] * MS, (window[1] - window[0]) * MS)]
                 + host, (window[0] * MS, window[1] * MS))


def busy(*spans_ms):
    return [Event(f"fusion.{i}", s * MS, (t - s) * MS)
            for i, (s, t) in enumerate(spans_ms)]


def ctx_of(tr):
    return types.SimpleNamespace(trace=tr, busy_s=trace.busy_s(tr))


def shares(tr) -> dict:
    ctx = ctx_of(tr)
    return {stage: r.read(ctx) for stage, r in readers().items()}


def test_innermost_span_wins():
    # serve 0..100 holds a micro-batch 10..29 (pad 10..12, dispatch 12..15,
    # wait 15..25, complete 25..29); the device runs 0..16 and 24..100, so
    # its one gap 16..24 lies inside wait, inside the micro-batch and serve
    host = [Event(spans.SERVE, 0, 100 * MS)] + microbatch(10)
    got = spans.idle_by_span(hand_trace(busy((0, 16), (24, 100)), host))
    assert got == {"engine.wait": pytest.approx(0.008)}


def test_gap_across_a_boundary_is_split_exactly():
    # gap 11..14 crosses pad|dispatch at 12 (1 ms under pad, 2 under
    # dispatch); gap 27..40 crosses complete's end at 29 and runs on under
    # serve alone; a midpoint rule would charge each gap whole to one span
    host = [Event(spans.SERVE, 0, 100 * MS)] + microbatch(10)
    tr = hand_trace(busy((0, 11), (14, 27), (40, 100)), host)
    assert spans.idle_by_span(tr) == {
        "engine.pad": pytest.approx(0.001), "engine.dispatch": pytest.approx(0.002),
        "engine.complete": pytest.approx(0.002), spans.SERVE: pytest.approx(0.011)}
    got = shares(tr)
    assert got["prep"] == pytest.approx(3.0) and got["complete"] == pytest.approx(2.0)
    assert got["loop"] == pytest.approx(11.0) and got["wait"] == 0.0


def test_bare_microbatch_and_poll_count_as_loop():
    # 5 ms of the micro-batch between wait and complete (25..30), and a
    # poll sleep 60..70: the device idles 25..30 and 58..72
    host = ([Event(spans.SERVE, 0, 100 * MS), Event("engine.poll", 60 * MS, 10 * MS)]
            + microbatch(10, gap=5))
    tr = hand_trace(busy((0, 25), (30, 58), (72, 100)), host)
    assert spans.idle_by_span(tr) == {
        "engine.microbatch": pytest.approx(0.005), "engine.poll": pytest.approx(0.010),
        spans.SERVE: pytest.approx(0.004)}
    got = shares(tr)
    assert got["loop"] == pytest.approx(19.0)
    assert got["prep"] == got["wait"] == got["complete"] == 0.0


def test_shares_sum_to_idle_when_serve_covers_the_window():
    # serve over the whole window, three micro-batches and a poll; the
    # device busy in scattered pieces, one of them past the window's end
    host = [Event(spans.SERVE, 0, 100 * MS), Event("engine.poll", 80 * MS, 5 * MS)]
    for t in (3, 25, 50):
        host += microbatch(t, gap=1)
    tr = hand_trace(busy((1, 4), (6, 18), (20, 31), (33, 45), (49, 66), (70, 79),
                         (90, 120)), host)
    got = shares(tr)
    assert all(v > 0 for v in got.values()), got
    assert sum(got.values()) == pytest.approx(roofline.idle_share(ctx_of(tr)))


def test_idle_outside_serve_is_charged_to_no_stage():
    # serve starts 20 ms into the window: the idle 0..20 belongs to no stage
    host = [Event(spans.SERVE, 20 * MS, 80 * MS)] + microbatch(30)
    tr = hand_trace(busy((40, 100)), host)
    got = shares(tr)
    assert sum(got.values()) == pytest.approx(roofline.idle_share(ctx_of(tr)) - 20.0)


@pytest.mark.parametrize("case", ["no_device_plane", "no_serve_span",
                                  "renamed_serve_span", "untraced"])
def test_readers_return_none(case):
    host = [Event(spans.SERVE, 0, 100 * MS)] + microbatch(10)
    dev = busy((0, 16), (24, 100))
    if case == "no_device_plane":
        dev = None
    elif case == "no_serve_span":
        host = host[1:]
    elif case == "renamed_serve_span":
        host = [Event("engine.serve_loop", 0, 100 * MS)] + host[1:]
    tr = hand_trace(dev, host)
    ctx = types.SimpleNamespace(trace=None) if case == "untraced" else ctx_of(tr)
    assert {s: r.read(ctx) for s, r in readers().items()} == dict.fromkeys(spans.STAGES)


# -- the program's own spans ---------------------------------------------------


@pytest.fixture
def tiny_engine(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    cfg = tiny.cell().config
    zoo = harness.build_zoo(cfg, 2 ** 32 + 5, harness.Clock(),
                            tuple(cfg["serving"]["score_buckets"]), 16 * 2 ** 30)
    yield zoo
    jax.clear_caches()


def test_serve_records_its_stage_spans(tiny_engine, tmp_path):
    from repro.serving.executor import Request

    eng, mids = tiny_engine.engine, tiny_engine.mids
    rng = np.random.default_rng(0)

    def doc():
        return rng.integers(0, tiny_engine.m.vocab, (1, 32), dtype=np.int32)

    eng.serve(horizon_s=0.0, warmup=doc(), drain=True)  # compile the buckets
    # earliest deadlines first: one row of every variant (the suffix bank),
    # then two rows of one variant (its own head)
    for k, mid in enumerate(mids):
        eng.submit(Request(mid, doc(), 0.0, 10.0 + k))
    for k in range(2):
        eng.submit(Request(mids[0], doc(), 0.0, 20.0 + k))
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        stats = eng.serve(horizon_s=1e9, drain=True)
    jax.profiler.stop_trace()
    path = trace.find_xplane(str(tmp_path))
    tr = trace.load(path)

    ours = sorted((e for e in tr.host if e.name in spans.SPANS),
                  key=lambda e: (e.start_ns, -e.dur_ns))

    def inside(e, outer):
        return outer.start_ns <= e.start_ns and e.end_ns <= outer.end_ns

    (serve,) = [e for e in ours if e.name == spans.SERVE]
    mbs = [e for e in ours if e.name == "engine.microbatch"]
    assert stats["completed"] == 6 and len(mbs) == stats["microbatches"] == 2
    assert tr.window[0] <= serve.start_ns and serve.end_ns <= tr.window[1]
    assert all(inside(mb, serve) for mb in mbs)
    stages = ["engine.pad", "engine.dispatch", "engine.wait", "engine.complete"]
    staged = [e for e in ours if e.name in stages]
    assert all(any(inside(e, mb) for mb in mbs) for e in staged)
    for mb in mbs:
        held = [e for e in staged if inside(e, mb)]
        assert [e.name for e in held] == stages
        assert all(a.end_ns <= b.start_ns for a, b in zip(held, held[1:]))

    # the micro-batch's kwargs, which trace.load leaves out, for operators
    kw = [dict(e.stats) for plane in ProfileData.from_file(path).planes
          for ln in plane.lines for e in ln.events if e.name == "engine.microbatch"]
    assert sorted((k["rows"], k["bucket"], k["banked"]) for k in kw) == [
        (2, 2, 0), (4, 4, 1)]
