"""The seams by which a model family and a traffic kind enter the
benchmark: the dense family reads as it did before it had a module of its
own, and a family and a traffic kind defined here, registered by module
name alone, run a cell through the harness."""
import dataclasses
import hashlib
import re
import sys
import time
import types

import jax
import numpy as np
import pytest

from bench import costs, harness, model, weights
from bench.drivers import open_streams
from bench.families import dense
from bench.references import dense as dense_reference
from bench.tests import tiny

# sha256 of the tiny zoo's leaves (keystr, then bytes, sorted by keystr) for
# seed 2**32 + 9, as the generator made them before it moved into
# bench/families/dense.py
WEIGHTS_SHA256 = {
    "float32": "d37fd7febf66c55c5ac7b2c464ec4e0098398c17b7b894064dbd828e555d24a7",
    "bfloat16": "f757b912d97983780601189ae82b7b16583f2ea851ca37a57b526bf9506d39b7",
}
SHARED = ["harness.py", "model.py", "weights.py", "costs.py", "traffic.py",
          "drivers/__init__.py", "drivers/open_streams.py",
          "drivers/closed_chat.py"]


@pytest.mark.parametrize("dtype", sorted(WEIGHTS_SHA256))
def test_dense_weights_are_bitwise_unchanged(dtype):
    m = model.from_config(tiny.cell(dtype).config)
    assert isinstance(m, dense.Dense) and m.family == "dense"
    trunk, heads = weights.zoo(m, 2 ** 32 + 9)
    h = hashlib.sha256()
    leaves = jax.tree_util.tree_leaves_with_path((trunk, heads))
    for p, x in sorted(leaves, key=lambda t: jax.tree_util.keystr(t[0])):
        h.update(jax.tree_util.keystr(p).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == WEIGHTS_SHA256[dtype]


def test_score_work_is_unchanged():
    """The work and useful FLOPs of the committed score cell's micro-batches
    (four rows of four variants, 128 tokens), as the harness counted them
    before the family had a module: 24 flash_attention calls and one bank a
    batch; a single-variant batch takes no bank."""
    m = model.from_config(tiny.load("configs", tiny.CONFIG))
    batches = [["a", "b", "c", "d"]] * 3 + [["a"]]
    work, useful, mixed = open_streams.batch_work(m, batches, (1, 2, 4), 128, True)
    assert mixed == 3
    assert work == {
        "flash_attention": [3 * 24 * 270_532_608 + 24 * 67_633_152,
                            3 * 24 * 8_388_608 + 24 * 2_097_152],
        "bank_matmul": [3 * 841_813_590_016,
                        3 * (8_388_608 + 1_644_167_168 + 822_083_584)]}
    assert useful == 13 * (317_303_291_904 + 128 * 411_041_792)
    unbanked, _, _ = open_streams.batch_work(m, batches, (1, 2, 4), 128, False)
    assert "bank_matmul" not in unbanked


@pytest.mark.parametrize("path", SHARED)
def test_shared_modules_name_no_family(path):
    text = (harness.BENCH / path).read_text()
    assert not re.search(r"dense", text, re.IGNORECASE), path


# -- a family and a traffic kind that exist only here --------------------------


@dataclasses.dataclass(frozen=True)
class Toy(dense.Dense):
    """The dense widths under a family name of its own."""


def toy_family() -> types.ModuleType:
    """A family whose heads are drawn at half the dense scale: its own
    generator, the program's dense adapter, the dense work counts."""
    fam = types.ModuleType("bench.families.toy")
    fam.from_config = lambda cfg: Toy(**{**dataclasses.asdict(dense.from_config(cfg)),
                                         "family": cfg["family"]})
    fam.trunk = dense.trunk

    def head(m, key):
        h = dense.head(m, key)
        h["lm_head"]["w"] = h["lm_head"]["w"] * 0.5
        return h

    fam.head = head
    for name in ("program_config", "token_flops",
                 "score_work", "decode_work"):
        setattr(fam, name, getattr(dense, name))
    return fam


def toy_driver() -> types.ModuleType:
    """A traffic kind: ``requests`` documents, round robin over the
    variants, served in one drained ``serve`` call; every one sampled."""
    drv = types.ModuleType("bench.drivers.toy_batch")

    def drive(z, mix, seed, seconds, clock, tracer):
        from repro.serving.executor import Request

        eng, m = z.engine, z.m
        rng = np.random.default_rng([seed, 9])
        toks = rng.integers(0, m.vocab, (mix["requests"], mix["prompt_tokens"]),
                            dtype=np.int32)
        eng.serve(horizon_s=0.0, warmup=toks[:1], drain=True)
        eng.completions = []
        for i, t in enumerate(toks):
            eng.submit(Request(z.mids[i % m.variants], t[None, :], 0.0, 1e9,
                               meta=i))
        tracer.start()
        t0 = time.perf_counter()
        stats = eng.serve(horizon_s=1e9, drain=True)
        window_s = time.perf_counter() - t0
        tracer.stop()
        got = {c.request.meta: np.asarray(c.result) for c in eng.completions}
        return harness.Window(
            t_window=t0, window_s=window_s, peak_bytes=0,
            attempted=len(toks), failed=len(toks) - len(got),
            end_to_end={"toy_docs_s": (len(got) / window_s, "docs/s")},
            counters={"completed": stats["completed"]}, work={},
            useful_flops=0.0,
            answers=[harness.Answer(i % m.variants, toks[i], got[i])
                     for i in sorted(got)])

    drv.drive = drive
    return drv


def test_a_family_and_a_traffic_kind_enter_by_module_name(monkeypatch):
    monkeypatch.setenv("REPRO_KERNEL_MODE", "ref")
    reference = types.ModuleType("bench.references.toy")
    reference.logits = dense_reference.logits
    monkeypatch.setitem(sys.modules, "bench.families.toy", toy_family())
    monkeypatch.setitem(sys.modules, "bench.references.toy", reference)
    monkeypatch.setitem(sys.modules, "bench.drivers.toy_batch", toy_driver())
    cfg = dict(tiny.cell().config, family="toy")
    cell = harness.Cell("toy-cell", 1, cfg,
                        {"kind": "toy_batch", "requests": 8, "prompt_tokens": 16},
                        {}, ["toy_docs_s", "setup_s"], dict(tiny.LIMITS))
    r = harness.run_cell(cell, 2 ** 33 + 1, 1.0, False, time.perf_counter())
    jax.clear_caches()
    assert r["correct"] is True and r["attempted"] == 8 and r["failed"] == 0
    assert set(r["metrics"]) == {"toy_docs_s", "setup_s"}
    assert r["checks"]["logit_err"]["value"] < tiny.LIMITS["logit_err"]
    # the toy's own generator made the heads the check rebuilt
    m = model.from_config(cfg)
    _, heads = weights.zoo(m, 3)
    _, dense_heads = weights.zoo(dense.from_config(tiny.cell().config), 3)
    assert np.array_equal(np.asarray(heads[0]["lm_head"]["w"]),
                          np.asarray(dense_heads[0]["lm_head"]["w"]) * 0.5)
    assert costs.head_token_flops(m) == 2 * m.d_model * m.vocab
