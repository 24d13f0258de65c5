"""A whole run of the chat decode cell on the CPU at tiny size, past the
chip check: correct when the program is sound, not correct with the timed
path broken underneath (bench/tests/tiny.py names the faults), and not
correct with the control's answers in the program's place.  One chip, so
no exchange between chips to leave out."""
import jax
import pytest

from bench.tests import tiny


@pytest.fixture(autouse=True)
def ref_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    yield
    jax.clear_caches()  # nothing traced through a broken program outlives it


def run(**kw):
    return tiny.run(name=tiny.CHAT_CELL, **kw)


def test_sound_program_is_correct():
    r = run()
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 8
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"logit_err", "token_gap", "lost_in_flight",
                                "pool_faults", "sample_missing", "late_starts"}
    for v in r["checks"].values():
        assert v["value"] <= v["limit"]
    assert r["metrics"]["decode_tok_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["answer", "half_batch", "stale_kv",
                                   "wrong_head", "reused_slot"])
def test_broken_program_is_not_correct(fault, monkeypatch):
    tiny.break_program(monkeypatch, fault)
    r = run()
    assert r["correct"] is False
    assert any(v["value"] > v["limit"] for v in r["checks"].values())


def test_control_fails_the_limit():
    """The control (the reference in float8, in the program's place) comes
    out not correct against the committed limits, over the same prompts and
    tokens on which the program, in the configuration's bfloat16, comes out
    correct; on the chip at the cell's size by ``bench/control.py``."""
    r = run(control=True, dtype="bfloat16")
    limits = tiny.load("limits", tiny.CHAT_CELL)
    assert r["correct"] is True
    assert r["checks"]["logit_err"]["limit"] == limits["logit_err"]
    assert r["control"]["correct"] is False
    assert any(r["control"]["checks"][k]["value"] > limits[k]
               for k in ("logit_err", "token_gap"))
