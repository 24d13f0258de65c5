"""The one traffic generator: reads a mix's parameters, makes its requests.

A mix of kind ``open_streams`` (:func:`open_schedule`): streams at fixed
rates (cameras, each frame scored by one model).  ``streams`` streams share ``rate_per_s``
equally; each sends a ``prompt_tokens``-long document every
``streams / rate_per_s`` seconds.

``frame_models`` (default 1) streams share one phase: one camera whose
every frame ``frame_models`` models score.  The cameras' phases are spaced
equally through the period, and stream ``i`` belongs to variant class
``i mod variants``, so with ``frame_models`` equal to the variants each
frame goes to every variant at once.

The seed only names the classes (a permutation of the variants) and draws
the token ids, so every ``--seed`` offers the same arrivals and sizes, and
the same requests share a variant: each micro-batch the engine forms mixes
as many variants under every seed.

A mix of kind ``closed_chat`` (:class:`ChatClients`): clients, each bound
to one variant and sending its next request as soon as the last one
retires.  ``clients_per_variant`` gives the clients of each variant class,
most popular first.  Prompt and output lengths are integers drawn from
log-normals with the mix's ``mean`` and ``sigma`` (of the log), held to
``[min, max]``.  Each client follows one fixed list of
lengths; the lists are drawn once from ``length_seed``.  The seed names
the classes (which variant is how popular), deals the lists to the
clients and draws the token ids, so every ``--seed`` asks for the same
lengths in another order and the decoder does the same work.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Item:
    index: int
    variant: int
    tokens: np.ndarray  # (prompt,) int32 token ids
    due_s: float  # send time from the window's start


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed, stream])


def open_schedule(mix: dict, seed: int, seconds: float, variants: int,
                  vocab: int) -> list:
    """Every request due in ``[0, seconds)``, in due order."""
    n, per_cam = mix["streams"], mix.get("frame_models", 1)
    if n % per_cam:
        raise ValueError(f"{n} streams do not split into cameras of {per_cam}")
    cams = n // per_cam
    period = n / mix["rate_per_s"]
    phases = np.repeat((np.arange(cams) + 0.5) * (period / cams), per_cam)
    variant = _rng(seed, 0).permutation(variants)[np.arange(n) % variants]
    due = sorted((float(ph + k * period), i)
                 for i, ph in enumerate(phases)
                 for k in range(int(np.ceil((seconds - ph) / period))))
    toks = _rng(seed, 1).integers(0, vocab, (len(due), mix["prompt_tokens"]),
                                  dtype=np.int32)
    return [Item(j, int(variant[i]), toks[j], due_s=t)
            for j, (t, i) in enumerate(due)]


@dataclasses.dataclass(frozen=True)
class Chat:
    client: int
    k: int  # the client's k-th request, from 0
    variant: int
    prompt: np.ndarray  # (prompt,) int32 token ids
    max_new: int  # tokens to generate


def log_normal(rng: np.random.Generator, mean: float, sigma: float, lo: int,
               hi: int) -> int:
    """One integer: a log-normal draw of mean ``mean`` whose log has
    spread ``sigma``, rounded and held to [lo, hi] (a front end cuts a
    longer prompt or answer at its limit)."""
    x = rng.lognormal(np.log(mean) - sigma ** 2 / 2, sigma)
    return int(np.clip(np.rint(x), lo, hi))


class ChatClients:
    """The requests of a ``closed_chat`` mix: :meth:`request` gives client
    ``c``'s ``k``-th, the same for the same seed in any order of asking."""

    def __init__(self, mix: dict, seed: int, variants: int, vocab: int):
        per = mix["clients_per_variant"]
        if len(per) != variants:
            raise ValueError(f"clients_per_variant has {len(per)} classes "
                             f"for {variants} variants")
        if mix["prompt_tokens"]["min"] < 2:
            # the sampled rows' logits are recorded from their second step
            raise ValueError("closed_chat prompts need at least 2 tokens")
        self.mix, self.seed, self.vocab = mix, seed, vocab
        self.variant = _rng(seed, 0).permutation(variants)[
            np.repeat(np.arange(variants), per)]
        self.lengths = _rng(seed, 5).permutation(len(self.variant))  # client -> its list

    @property
    def clients(self) -> int:
        return len(self.variant)

    @property
    def longest(self) -> int:
        """KV positions the longest request can hold."""
        return self.mix["prompt_tokens"]["max"] + self.mix["output_tokens"]["max"] - 1

    def sizes(self, c: int, k: int) -> tuple:
        """(prompt tokens, output tokens) of client ``c``'s ``k``-th
        request."""
        rng = np.random.default_rng([self.mix["length_seed"], int(self.lengths[c]), k])
        p, o = (self.mix[key] for key in ("prompt_tokens", "output_tokens"))
        return (log_normal(rng, p["mean"], p["sigma"], p["min"], p["max"]),
                log_normal(rng, o["mean"], o["sigma"], o["min"], o["max"]))

    def request(self, c: int, k: int) -> Chat:
        p, o = self.sizes(c, k)
        toks = np.random.default_rng([self.seed, 3, c, k]).integers(
            0, self.vocab, p, dtype=np.int32)
        return Chat(c, k, int(self.variant[c]), toks, o)


def sample_indices(rng: np.random.Generator, by_variant: dict,
                   per_variant: int) -> list:
    """A seeded sample: ``per_variant`` indices from each variant's list."""
    out = set()
    for v in sorted(by_variant):
        pool = by_variant[v]
        out.update(int(i) for i in rng.choice(pool, min(per_variant, len(pool)),
                                              replace=False))
    return sorted(out)
