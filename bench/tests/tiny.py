"""Tiny cells for the CPU tests: the benchmark's configuration and mix cut
to a width and length the CPU runs in seconds, through the same harness,
reference and metric readers."""
from __future__ import annotations

import dataclasses
import json
import time

import jax

from bench import harness

CONFIG, MIX, CELL = "stablelm-1.6b-zoo4", "camera-128", "stablelm-score-uniform"
CHAT_MIX, CHAT_CELL = "chat-16", "stablelm-decode-chat"
TINY = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, vocab_size=512)
# each cell's mix and what it is cut to, and its end-to-end metrics
CELLS = {
    CELL: (MIX, dict(rate_per_s=32.0, prompt_tokens=32),
           ["score_docs_s", "hbm_peak_gb", "setup_s"]),
    # 8 clients, 3, 2, 2 and 1 of the variants, through buckets of 2, 4
    # and 8 rows; a request holds at most 8 + 16 - 1 = 23 of its 24
    # positions, and the window opens after 12 steps
    CHAT_CELL: (CHAT_MIX, dict(clients_per_variant=[3, 2, 2, 1],
                               prompt_tokens=dict(mean=4, sigma=1.0, min=2, max=8),
                               output_tokens=dict(mean=8, sigma=1.0, min=1, max=16),
                               warm_steps=12, sample_steps=8,
                               decoder=dict(page_size=4, num_pages=48,
                                            max_slots=8, max_len=24,
                                            buckets=[2, 4, 8])),
                ["decode_tok_s", "hbm_peak_gb", "setup_s"]),
}
# limit for the tiny float32 cells, where the program and the reference
# agree to rounding (~1e-6) and every broken program reads above 0.1
LIMITS = {"logit_err": 0.05}


def load(kind: str, name: str) -> dict:
    """A configuration (``configs``), traffic mix (``traffic``) or limits
    (``limits``) file."""
    return json.loads((harness.BENCH / kind / f"{name}.json").read_text())


def cell(dtype: str = "float32", config_overrides: dict = None,
         name: str = CELL) -> harness.Cell:
    """The configuration under the cell's mix, cut to tiny sizes: in
    float32 the program and the reference agree to rounding, and with
    ``dtype`` the configuration's own ``bfloat16`` the cell keeps the
    committed limits."""
    mix_name, cut, end_to_end = CELLS[name]
    cfg = load("configs", CONFIG)
    cfg.update(TINY, torch_dtype=dtype)
    cfg.update(config_overrides or {})
    mx = load("traffic", mix_name)
    mx.update(cut)
    committed = harness._limits(harness.BENCH / "limits" / f"{name}.json")
    limits = ({k: LIMITS["logit_err"] for k in committed} if dtype == "float32"
              else committed)
    return harness.Cell(name, 1, cfg, mx, {}, list(end_to_end), limits)


def dumps(result) -> str:
    return json.dumps(result, default=float)


def run(seconds: float = 1.0, seed: int = 2 ** 32 + 9, mix_overrides: dict = None,
        control: bool = False, dtype: str = "float32", name: str = CELL) -> dict:
    """One run of the tiny cell through the harness, past the chip check
    (``harness.open_chip``)."""
    c = cell(dtype, name=name)
    c.mix.update(mix_overrides or {})
    return harness.run_cell(c, seed, seconds, False, time.perf_counter(),
                            control=control)


def break_program(monkeypatch, fault: str) -> None:
    """Break the timed path underneath the harness:

    ``answer``       every served logit row altered where the head makes it
                     (token 7's logit raised by 5), on both head paths
    ``member_head``  the same, on the per-member head alone, which serves
                     the micro-batches whose rows all belong to one variant
    ``bank_head``    the same, on the suffix bank alone, which serves the
                     micro-batches that mix variants
    ``half_batch``   the rows of the second half of every batch left out of
                     the trunk (their hidden states zeroed), scoring and
                     decoding
    ``stale_kv``     the decode step's KV page write skipped, so every row
                     attends to what its pages held before
    ``wrong_head``   the suffix bank's member axis rolled by one, so each
                     row's logits come from another variant's head
    ``reused_slot``  a request admitted into a slot that a request retired
                     from at the step before takes that request's last
                     token as its first input; a client's first request,
                     admitted into a fresh slot, is untouched
    """
    from repro.models import layers, transformer
    from repro.models.registry import DenseLMAdapter

    def bump(x):
        return x.at[..., 7].add(5.0)

    if fault == "answer":
        orig = layers.unembed
        monkeypatch.setattr(layers, "unembed", lambda *a, **k: bump(orig(*a, **k)))
    elif fault in ("member_head", "bank_head"):
        build = DenseLMAdapter._build_split

        def broken(self, cfg):
            sp = build(self, cfg)
            if fault == "member_head":
                return dataclasses.replace(
                    sp, suffix=lambda p, f, _s=sp.suffix: bump(_s(p, f)))
            return dataclasses.replace(
                sp, bank_suffix=lambda p, f, _s=sp.bank_suffix: bump(_s(p, f)))
        monkeypatch.setattr(DenseLMAdapter, "_build_split", broken)
    elif fault == "half_batch":
        trunk, step = transformer.trunk, transformer.paged_trunk_step

        def drop(h):
            return h.at[-(-h.shape[0] // 2):].set(0)

        def half_step(*a, **k):
            h, pool = step(*a, **k)
            return drop(h), pool
        monkeypatch.setattr(transformer, "trunk", lambda *a, **k: drop(trunk(*a, **k)))
        monkeypatch.setattr(transformer, "paged_trunk_step", half_step)
    elif fault == "stale_kv":
        monkeypatch.setattr(transformer, "_paged_write",
                            lambda pool_k, pool_v, *a, **k: (pool_k, pool_v))
    elif fault == "reused_slot":
        from repro.serving.decode import StreamingDecoder

        cls = StreamingDecoder
        retire, admit, step, run = cls._retire, cls._admit, cls.step, cls.run
        stale: list = []  # last tokens of the requests retired at the last step

        def _retire(self, rid):
            stale.append(self.slots[rid].last_token)
            retire(self, rid)

        def _admit(self):
            had = set(self.slots)
            admit(self)
            for rid in [r for r in self.slots if r not in had]:
                if stale:
                    self.slots[rid].prompt[0] = stale.pop()

        def _step(self):
            stale.clear()
            step(self)

        def _run(self, *a, **k):
            stale.clear()
            return run(self, *a, **k)
        for name, fn in (("_retire", _retire), ("_admit", _admit),
                         ("step", _step), ("run", _run)):
            monkeypatch.setattr(cls, name, fn)
    elif fault == "wrong_head":
        bank = transformer.bank_head
        monkeypatch.setattr(transformer, "bank_head",
                            lambda *a, **k: jax.numpy.roll(bank(*a, **k), 1, axis=0))
    else:
        raise ValueError(fault)
