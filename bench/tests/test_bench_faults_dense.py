"""A whole run of the scoring cell on the CPU at tiny size, past the chip
check: correct when the program is sound, not correct with the timed path
broken underneath (bench/tests/tiny.py names the faults), and not correct
with the control's answers in the program's place.  One chip, so no
exchange between chips to leave out."""
import jax
import pytest

from bench.tests import tiny

# enough load that micro-batches fill and mix variants, so half of one can
# be left out and the suffix bank serves them
BUSY = {"rate_per_s": 1600.0}
# one stream a camera, so at light load each micro-batch holds one variant
# and takes the per-member head (the committed mix sends every frame to all
# variants at once, which the suffix bank serves)
SINGLE = {"frame_models": 1}


@pytest.fixture(autouse=True)
def ref_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    yield
    jax.clear_caches()  # nothing traced through a broken program outlives it


@pytest.mark.parametrize("busy", [None, BUSY, SINGLE],
                         ids=["light", "busy", "single"])
def test_sound_program_is_correct(busy):
    r = tiny.run(mix_overrides=busy)
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"
    for v in r["checks"].values():
        assert v["value"] <= v["limit"]


@pytest.mark.parametrize("fault, busy", [
    ("answer", None), ("member_head", SINGLE), ("bank_head", None),
    ("bank_head", BUSY),
    ("half_batch", BUSY),
])
def test_broken_program_is_not_correct(fault, busy, monkeypatch):
    tiny.break_program(monkeypatch, fault)
    r = tiny.run(mix_overrides=busy)
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["checks"].values())


def test_control_fails_the_limit():
    """The control (the reference in float8, in the program's place) comes
    out not correct against the committed limit, over the same prompts on
    which the program, in the configuration's bfloat16, comes out correct;
    on the chip at the cell's size by ``bench/control.py``."""
    r = tiny.run(control=True, dtype="bfloat16", mix_overrides=BUSY)
    limit = tiny.load("limits", tiny.CELL)["logit_err"]
    assert r["correct"] is True and r["checks"]["logit_err"]["limit"] == limit
    assert r["control"]["correct"] is False
    assert r["control"]["checks"]["logit_err"]["value"] > limit


def test_sample_holds_a_single_variant_batch():
    """The sink keeps the seeded sample and one request of the first
    micro-batch at or past ``single_from`` whose rows all belong to one
    variant (those take the per-member head, not the suffix bank), drops
    every other result, and hands the kept ones over only when asked."""
    import numpy as np

    from bench import harness
    from bench.drivers import open_streams
    from repro.serving.executor import Completion, Request

    clock = harness.Clock()
    clock()  # marks t0
    sink = open_streams._Sink(clock, sample={1, 6}, single_from=3)
    batches = [(0.1, [(0, "a"), (1, "b")]), (0.2, [(2, "c")]),
               (0.3, [(3, "a"), (4, "d")]), (0.4, [(5, "b"), (6, "b")]),
               (0.5, [(7, "c")])]
    for t, rows in batches:
        for i, iid in rows:
            sink.append(Completion(Request(iid, None, 0.0, 1.0, meta=i),
                                   np.full(2, float(i)), t))
    assert all(c.result is None for c in sink)
    # batch 0.2 holds one variant but lies before single_from; batch 0.4
    # is judged when batch 0.5 begins
    assert sink.single == 5 and sorted(sink.held) == [1, 5, 6]
    got = sink.results()
    assert sorted(got) == [1, 5, 6] and sink.single == 5
    assert all(np.array_equal(got[i], np.full(2, float(i))) for i in got)
    assert [len(v) for v in sink.batches.values()] == [2, 1, 2, 2, 1]
