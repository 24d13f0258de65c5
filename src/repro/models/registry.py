"""Uniform adapters over the model zoo.

Two registries live here:

* **ModelFamily** — the training/serving call surface (init / loss / prefill /
  decode_step) the trainer, server and dry-run consume:

      fam = get_family("moe")
      params = fam.init(cfg, key)
      loss   = fam.loss(cfg, params, batch)          # train_step target
      logits, cache = fam.prefill(cfg, params, ...)  # serving
      logits, cache = fam.decode_step(cfg, params, cache, tokens)

* **MergeableAdapter** (DESIGN.md P3) — the model-facing contract of the
  merge pipeline.  GEMEL's claim is that architectural *similarity*, not a
  specific architecture, makes layer sharing profitable (§4), so everything
  the planner / calibrator / serving engine needs from a model is behind one
  interface:

      a = get_adapter("small_cnn")
      recs  = a.records(cfg, params, model_id)        # signature extraction
      acts  = a.layer_activations(cfg, params, batch) # CKA calibration taps
      split = a.split(cfg)                            # prefix/suffix serving
      reg   = a.registered(cfg, model_id, key)        # planner retraining

  ``repro.core`` and ``repro.serving`` consume adapters only — never a
  family's private helpers (scripts/ci.sh greps for violations).

``batch`` layouts per family (all include "labels" and optional "mask"):
    dense/moe/ssm/griffin:  {"tokens": (B,S) i32}
    vlm:                    + {"patch_embeds": (B,P,d) f}
    encdec:                 {"src_embeds": (B,Ssrc,d) f, "tokens": (B,Stgt)}
    small_cnn:              {"images": (B,32,32,3) f}
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models import encdec, griffin, moe, ssm, transformer, vision, vlm


@dataclasses.dataclass(frozen=True)
class ModelFamily:
    name: str
    config_cls: type
    init: Callable
    loss: Callable
    forward: Callable
    init_cache: Optional[Callable] = None
    decode_step: Optional[Callable] = None
    prefill: Optional[Callable] = None
    has_decode: bool = True


FAMILIES: dict[str, ModelFamily] = {
    "dense": ModelFamily(
        "dense", transformer.DenseLMConfig, transformer.init, transformer.loss_fn,
        transformer.forward, transformer.init_cache, transformer.decode_step,
        transformer.prefill,
    ),
    "moe": ModelFamily(
        "moe", moe.MoELMConfig, moe.init, moe.loss_fn, moe.forward,
        moe.init_cache, moe.decode_step, moe.prefill,
    ),
    "ssm": ModelFamily(
        "ssm", ssm.MambaConfig, ssm.init, ssm.loss_fn, ssm.forward,
        ssm.init_cache, ssm.decode_step, ssm.prefill,
    ),
    "hybrid": ModelFamily(
        "hybrid", griffin.GriffinConfig, griffin.init, griffin.loss_fn,
        griffin.forward, griffin.init_cache, griffin.decode_step, griffin.prefill,
    ),
    "vlm": ModelFamily(
        "vlm", vlm.VLMConfig, vlm.init, vlm.loss_fn, vlm.forward,
        vlm.init_cache, vlm.decode_step, vlm.prefill,
    ),
    "encdec": ModelFamily(
        "encdec", encdec.EncDecConfig, encdec.init, encdec.loss_fn,
        encdec.forward, None, encdec.decode_step, encdec.prefill,
    ),
    # small_cnn is just another family: the GEMEL vision models reach the
    # pipeline through the same registries as the LM zoo.
    "small_cnn": ModelFamily(
        "small_cnn", vision.SmallCNNConfig, vision.init_small_cnn,
        vision.small_cnn_loss, vision.small_cnn_forward, has_decode=False,
    ),
}


def get_family(name: str) -> ModelFamily:
    return FAMILIES[name]


# ---------------------------------------------------------------------------
# MergeableAdapter — the merge pipeline's model-facing contract (DESIGN.md P3)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class PrefixSplit:
    """A cfg-bound split of one model into a mergeable trunk and a private
    head.  ``prefix``/``suffix`` take (params, x) / (params, feats) — the
    ``ModelProgram`` call shape — and ``suffix(prefix(x))`` must equal the
    adapter's ``forward`` bitwise (tests/test_adapters.py).  The callables are
    cached per (adapter, cfg), so every group member hands the serving engine
    the *same* function objects and a shared-prefix group compiles once.

    The optional suffix-bank tier (DESIGN.md S2): ``suffix_paths`` are the
    flat param paths the suffix reads, ``suffix_signature`` a hashable
    congruence fingerprint (equal fingerprints => the members' suffix leaves
    stack into one bank), and ``bank_suffix(bank_params, feats) -> (N, ...)``
    the fused fan-out — ONE dispatch for every private head of a merged
    group, bitwise identical to the per-member path in ``ref`` kernel mode."""

    prefix: Callable  # (params, x) -> feats
    suffix: Callable  # (params, feats) -> out
    prefix_paths: frozenset  # flat param paths the prefix reads
    suffix_paths: Optional[frozenset] = None  # flat paths the suffix reads
    suffix_signature: Optional[tuple] = None  # bank-congruence fingerprint
    bank_suffix: Optional[Callable] = None  # (bank_params, feats) -> (N, ...)


@dataclasses.dataclass(frozen=True)
class DecodeSplit:
    """Streaming-decode serving surface of a splittable adapter (DESIGN.md
    D1) — the token-by-token twin of :class:`PrefixSplit`.

    ``trunk_step(params, pool, tables, lengths, tokens) -> (hidden, pool)``
    advances every row of a paged batch by ONE token through the mergeable
    trunk; ``head(params, hidden) -> logits`` is the private fan-out, with
    the same op sequence as the tail of ``step`` so trunk_step + head is
    bitwise-identical to the composed step.  ``step`` is the full paged
    per-model path (singleton groups); ``step_unpaged`` /
    ``init_cache(batch, max_len)`` are the family's contiguous-cache decode
    — the per-request baseline lane and the bitwise replay oracle.
    ``bank_head(bank_params, hidden) -> (N, B, 1, V)``, when set, fans every
    congruent private head out in one dispatch (DESIGN.md S2).

    ``trunk_paths`` / ``head_paths`` / ``head_signature`` are identical to
    the PrefixSplit tiers — decode grouping reuses the engine's
    shared-prefix congruence machinery unchanged."""

    trunk_step: Callable  # (params, pool, tables, lengths, tokens)
    head: Callable  # (params, hidden) -> logits
    step: Callable  # (params, pool, tables, lengths, tokens) paged full step
    step_unpaged: Callable  # (params, cache, tokens) -> (logits, cache)
    init_pool: Callable  # (num_pages, page_size) -> pool pytree
    init_cache: Callable  # (batch, max_len) -> contiguous cache
    trunk_paths: frozenset
    head_paths: Optional[frozenset] = None
    head_signature: Optional[tuple] = None
    bank_head: Optional[Callable] = None  # (bank_params, hidden) -> (N, ...)
    # chunked prompt admission (optional): (params, pool, tables, lengths,
    # tokens (B, C)) -> (hidden (B, C, d), pool) — C sequential trunk steps
    # in ONE dispatch, bitwise identical to C single-token trunk_step calls
    prefill_chunk: Optional[Callable] = None


class MergeableAdapter:
    """One model family's view of the merge pipeline.

    Capability tiers (README has the family matrix):

    * **merge** (every adapter): ``records`` — one :class:`LayerRecord` per
      param leaf via the shared ``records_from_params`` path, so spec- and
      params-derived records flow through identical grouping machinery.
      Works on ``eval_shape`` trees — descriptor-scale planning allocates
      nothing.
    * **calibrate** (``can_calibrate``): ``calibration_batch`` +
      ``layer_activations`` — activation probes keyed by param-path prefix
      (``core.policy.default_layer_key``) feeding the CKA similarity scorer
      and the coherence surrogate trainer, plus ``loss``/``accuracy`` so
      ``StagedPlanner`` retraining is family-agnostic (``registered``).
    * **split-serve** (``can_split``): ``split(cfg)`` — prefix/suffix
      callables + prefix paths for the engine's shared-prefix batched
      execution (``ModelProgram.from_adapter``).
    * **decode-serve** (``can_decode``): ``decode_split(cfg)`` — paged
      trunk-step/head callables for the streaming decode loop
      (``serving.decode``, DESIGN.md D1).
    """

    name: str = "adapter"
    family: Optional[str] = None  # FAMILIES key this adapter wraps, if any
    can_calibrate: bool = False
    can_split: bool = False
    can_decode: bool = False

    def __init__(self):
        self._bound: dict = {}  # (kind, cfg) -> cached cfg-bound artifact

    # -- model surface --------------------------------------------------------

    def default_config(self):
        raise NotImplementedError(f"{self.name}: no default config bound")

    def init(self, cfg, key):
        raise NotImplementedError(f"{self.name}: no init bound")

    def forward(self, cfg, params, x):
        raise NotImplementedError(f"{self.name}: no forward bound")

    def loss(self, cfg, params, batch):
        raise NotImplementedError(f"{self.name}: no loss bound")

    def forward_batch(self, cfg, params, batch: dict):
        """Logits for a calibration batch in the family's batch layout (see
        module docstring).  The default covers token-only LMs; families with
        extra inputs or tuple outputs override this, and :meth:`accuracy`
        stays shared."""
        out = self.forward(cfg, params, batch["tokens"])
        return out[0] if isinstance(out, tuple) else out

    def accuracy(self, cfg, params, batch):
        """Default argmax-vs-labels accuracy derived from ``forward`` — the
        DriftMonitor tier works on every registered family without a
        family-specific override (satellite of ISSUE 10; this used to be a
        bare NotImplementedError)."""
        logits = self.forward_batch(cfg, params, batch)
        vocab = getattr(cfg, "vocab_size", None)
        if vocab:
            logits = logits[..., :vocab]
        pred = jnp.argmax(logits, axis=-1)
        correct = (pred == batch["labels"]).astype(jnp.float32)
        mask = batch.get("mask")
        if mask is not None:
            return jnp.sum(correct * mask) / jnp.maximum(jnp.sum(mask), 1.0)
        return jnp.mean(correct)

    # -- merge: signature extraction ------------------------------------------

    def records(self, cfg, params, model_id: str) -> list:
        """LayerRecords for grouping — the ONE records path every family
        shares (kind-from-path, shape, dtype signatures)."""
        from repro.core.signatures import records_from_params

        return records_from_params(params, model_id)

    def eval_params(self, cfg):
        """Parameter tree of ShapeDtypeStructs — records/prefix-path
        extraction without allocating weights (pod-scale sizing)."""
        return jax.eval_shape(lambda: self.init(cfg, jax.random.PRNGKey(0)))

    # -- calibrate ------------------------------------------------------------

    def calibration_batch(self, cfg, key, n: int) -> dict:
        """A synthetic batch usable by ``loss``/``accuracy``/
        ``layer_activations`` — run the SAME batch through every candidate
        model so CKA compares responses to identical inputs."""
        raise NotImplementedError(f"{self.name}: no calibration support")

    def layer_activations(self, cfg, params, batch: dict) -> dict:
        """{layer_key: (N, ...) activations} where ``layer_key`` is the
        param-path prefix ``core.policy.default_layer_key`` maps record
        paths onto (conformance-tested per family)."""
        raise NotImplementedError(f"{self.name}: no calibration support")

    # -- split-serve ----------------------------------------------------------

    def split(self, cfg) -> PrefixSplit:
        """Prefix/suffix serving split, cached per cfg (see
        :class:`PrefixSplit` for why caching matters).  Splits that declare
        ``suffix_paths`` get a generic ``suffix_signature`` filled in, so
        every splittable adapter is bank-eligible by default."""
        key = ("split", self._cfg_key(cfg))
        sp = self._bound.get(key)
        if sp is None:
            sp = self._build_split(cfg)
            if sp.suffix_paths is not None and sp.suffix_signature is None:
                sp = dataclasses.replace(
                    sp, suffix_signature=self.suffix_signature(cfg, sp))
            self._bound[key] = sp
        return sp

    def suffix_signature(self, cfg, sp: Optional[PrefixSplit] = None):
        """Hashable congruence fingerprint of the private head: adapter
        name, the cfg identity, and (path, shape, dtype) of every suffix
        leaf.  Two programs with equal fingerprints stack their suffix
        weights into one bank and the engine fans them out in a single
        dispatch (DESIGN.md S2); unequal fingerprints fall back to the
        per-member suffix path.  The cfg term matters: the bank executes
        every member through the LEAD member's suffix closure, so heads that
        are merely shape-congruent but semantically different under their
        cfg (norm kind, logit softcap, ...) must never compare equal —
        value-equal frozen-dataclass cfgs do, distinct semantics don't."""
        from repro.utils.tree import flatten_paths

        sp = self.split(cfg) if sp is None else sp
        if sp.suffix_paths is None:
            return None
        flat = flatten_paths(self.eval_params(cfg))
        return (self.name, self._cfg_key(cfg), tuple(sorted(
            (p, tuple(flat[p].shape), str(flat[p].dtype))
            for p in sp.suffix_paths)))

    def _build_split(self, cfg) -> PrefixSplit:
        raise NotImplementedError(f"{self.name}: no prefix/suffix split")

    def decode_split(self, cfg) -> DecodeSplit:
        """Streaming-decode split, cached per cfg like :meth:`split` so all
        members of a group hand the decode loop the same function objects
        (one jit trace per group, not per member)."""
        key = ("decode_split", self._cfg_key(cfg))
        ds = self._bound.get(key)
        if ds is None:
            ds = self._build_decode_split(cfg)
            self._bound[key] = ds
        return ds

    def _build_decode_split(self, cfg) -> DecodeSplit:
        raise NotImplementedError(f"{self.name}: no streaming decode split")

    def bound_forward(self, cfg) -> Callable:
        """(params, x) forward closure, cached per cfg so instances of one
        family share a single callable (and therefore jit traces)."""
        key = ("forward", self._cfg_key(cfg))
        fn = self._bound.get(key)
        if fn is None:
            def fn(params, x, _self=self, _cfg=cfg):
                return _self.forward(_cfg, params, x)

            self._bound[key] = fn
        return fn

    @staticmethod
    def _cfg_key(cfg):
        try:
            hash(cfg)
            return cfg
        except TypeError:
            return id(cfg)

    # -- planner glue ---------------------------------------------------------

    def registered(self, cfg, model_id: str, key, n_batches: int = 2,
                   batch_size: int = 8, accuracy_target: float = 0.9,
                   original_accuracy: Optional[float] = None):
        """A ``RegisteredModel`` whose loss/accuracy/data all come from this
        adapter — what makes ``StagedPlanner`` + ``MergeTrainer`` retraining
        family-agnostic."""
        from repro.core.validation import RegisteredModel

        ks = jax.random.split(key, n_batches + 1)
        train = [self.calibration_batch(cfg, ks[i], batch_size)
                 for i in range(n_batches)]
        val = self.calibration_batch(cfg, ks[-1], batch_size)
        return RegisteredModel(
            model_id,
            lambda p, b: self.loss(cfg, p, b),
            lambda p, b: self.accuracy(cfg, p, b),
            lambda epoch: train, val, accuracy_target, original_accuracy,
        )


# ---------------------------------------------------------------------------
# Concrete adapters
# ---------------------------------------------------------------------------


class SmallCNNAdapter(MergeableAdapter):
    """The paper's reduced-scale vision models — full merge / calibrate /
    split-serve support, now reached exclusively through this contract."""

    name = "small_cnn"
    family = "small_cnn"
    can_calibrate = True
    can_split = True

    def default_config(self):
        return vision.SmallCNNConfig(task="classification", n_classes=4,
                                     depth=1, width=8, n_stages=2)

    def init(self, cfg, key):
        return vision.init_small_cnn(cfg, key)

    def forward(self, cfg, params, x):
        return vision.small_cnn_forward(cfg, params, x)

    def loss(self, cfg, params, batch):
        return vision.small_cnn_loss(cfg, params, batch)

    def accuracy(self, cfg, params, batch):
        return vision.small_cnn_accuracy(cfg, params, batch)

    def calibration_batch(self, cfg, key, n: int) -> dict:
        kx, ky, kl = jax.random.split(key, 3)
        batch = {"images": jax.random.normal(kx, (n, 32, 32, 3), cfg.dtype)}
        if cfg.task == "classification":
            batch["labels"] = jax.random.randint(ky, (n,), 0, cfg.n_classes)
        else:
            g = 32 // (2 ** (cfg.n_stages - 1))
            batch["cls_targets"] = jax.random.randint(
                ky, (n, g, g, cfg.n_anchors), 0, cfg.n_classes)
            batch["loc_targets"] = jax.random.normal(
                kl, (n, g, g, cfg.n_anchors * 4))
        return batch

    def layer_activations(self, cfg, params, batch: dict) -> dict:
        return vision.small_cnn_layer_activations(cfg, params, batch["images"])

    def _build_split(self, cfg) -> PrefixSplit:
        ep = self.eval_params(cfg)
        paths = vision.small_cnn_prefix_paths(cfg, ep)

        def prefix(params, x, _cfg=cfg):
            return vision.small_cnn_features(_cfg, params, x)

        def suffix(params, feats, _cfg=cfg):
            return vision.small_cnn_head(_cfg, params, feats)

        def bank_suffix(bank_params, feats, _cfg=cfg):
            return vision.small_cnn_bank_head(_cfg, bank_params, feats)

        return PrefixSplit(prefix, suffix, paths,
                           suffix_paths=vision.small_cnn_suffix_paths(cfg, ep),
                           bank_suffix=bank_suffix)


class _TokenLMAdapter(MergeableAdapter):
    """Shared plumbing for the token-in/logits-out LM adapters (dense, moe,
    ssm, hybrid): one calibration-batch layout so CKA compares every
    candidate's response to identical inputs, and the default
    argmax-vs-labels accuracy applies unchanged."""

    can_calibrate = True
    can_split = True
    can_decode = True

    def calibration_batch(self, cfg, key, n: int, seq: int = 8) -> dict:
        toks = jax.random.randint(key, (n, seq + 1), 0, cfg.vocab_size)
        return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


class DenseLMAdapter(_TokenLMAdapter):
    """Dense decoder-only transformers.  Calibration/split need per-layer
    param paths, so those tiers require ``scan_layers=False`` configs (the
    fine-tune-variant pod scenario); records work for any config, including
    scan-stacked full-scale ones (whole-stack groups)."""

    name = "dense"
    family = "dense"

    def default_config(self):
        return transformer.DenseLMConfig(
            name="tiny-lm", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
            head_dim=16, d_ff=64, vocab_size=64, vocab_multiple=32,
            tie_embeddings=False, scan_layers=False,
        )

    def init(self, cfg, key):
        return transformer.init(cfg, key)

    def forward(self, cfg, params, x):
        """One scoring/greedy-decode step: tokens (B, S) -> logits
        (B, S, V).  Composed as ``head(trunk(x))`` so the serving split is
        bitwise-identical by construction."""
        return transformer.head(cfg, params, transformer.trunk(cfg, params, x))

    def loss(self, cfg, params, batch):
        return transformer.loss_fn(cfg, params, batch)

    def layer_activations(self, cfg, params, batch: dict) -> dict:
        return transformer.layer_activations(cfg, params, batch["tokens"])

    def _build_split(self, cfg) -> PrefixSplit:
        ep = self.eval_params(cfg)
        paths = transformer.trunk_paths(ep)

        # named scopes label the trunk's and the bank's device operations
        # in a profiler trace
        def prefix(params, x, _cfg=cfg):
            with jax.named_scope("trunk"):
                return transformer.trunk(_cfg, params, x)

        def suffix(params, feats, _cfg=cfg):
            return transformer.head(_cfg, params, feats)

        if cfg.tie_embeddings:
            # tied heads read the shared embed table: banking would stack
            # the model's largest tensor N times and the vmap fallback is
            # only allclose-grade — stay on the per-member suffix path
            return PrefixSplit(prefix, suffix, paths)

        def bank_suffix(bank_params, feats, _cfg=cfg):
            with jax.named_scope("bank"):
                return transformer.bank_head(_cfg, bank_params, feats)

        return PrefixSplit(prefix, suffix, paths,
                           suffix_paths=transformer.head_paths(ep),
                           bank_suffix=bank_suffix)

    def _build_decode_split(self, cfg) -> DecodeSplit:
        sp = self.split(cfg)  # reuse the congruence tiers: same trunk/head

        def trunk_step(params, pool, tables, lengths, tokens, _cfg=cfg):
            return transformer.paged_trunk_step(
                _cfg, params, pool, tables, lengths, tokens)

        def head_fn(params, hidden, _cfg=cfg):
            return transformer.head(_cfg, params, hidden)

        def step(params, pool, tables, lengths, tokens, _cfg=cfg):
            return transformer.paged_decode_step(
                _cfg, params, pool, tables, lengths, tokens)

        def step_unpaged(params, cache, tokens, _cfg=cfg):
            return transformer.decode_step(_cfg, params, cache, tokens)

        def init_pool(num_pages, page_size, _cfg=cfg):
            return transformer.init_kv_pool(_cfg, num_pages, page_size)

        def init_cache(batch, max_len, _cfg=cfg):
            return transformer.init_cache(_cfg, batch, max_len)

        bank = None
        if sp.bank_suffix is not None:
            def bank(bank_params, hidden, _cfg=cfg):
                return transformer.bank_head(_cfg, bank_params, hidden)

        def prefill_chunk(params, pool, tables, lengths, tokens, _cfg=cfg):
            return transformer.paged_prefill_chunk(
                _cfg, params, pool, tables, lengths, tokens)

        return DecodeSplit(trunk_step, head_fn, step, step_unpaged,
                           init_pool, init_cache, sp.prefix_paths,
                           head_paths=sp.suffix_paths,
                           head_signature=sp.suffix_signature,
                           bank_head=bank,
                           prefill_chunk=prefill_chunk)


class SSMAdapter(_TokenLMAdapter):
    """Mamba selective-state-space LMs, full merge-and-serve tier (ISSUE 10):
    the recurrence runs through ``kernels.ops.mamba_scan``, so merged ssm
    serving exercises the Pallas kernel under every ``REPRO_KERNEL_MODE``.
    The decode state is dense-adjacent — per-layer ``(h (di, n), conv
    (d_conv-1, di))`` instead of a KV ring — and lives wholly in each
    request's FIRST page slot of the state pool."""

    name = "ssm"
    family = "ssm"

    def default_config(self):
        return ssm.MambaConfig(
            name="tiny-mamba", n_layers=2, d_model=32, d_inner=64, d_state=8,
            d_conv=4, dt_rank=8, vocab_size=64, vocab_multiple=32,
            tie_embeddings=False, scan_layers=False, chunk=16,
        )

    def init(self, cfg, key):
        return ssm.init(cfg, key)

    def forward(self, cfg, params, x):
        return ssm.head(cfg, params, ssm.trunk(cfg, params, x))

    def loss(self, cfg, params, batch):
        return ssm.loss_fn(cfg, params, batch)

    def layer_activations(self, cfg, params, batch: dict) -> dict:
        return ssm.layer_activations(cfg, params, batch["tokens"])

    def _build_split(self, cfg) -> PrefixSplit:
        ep = self.eval_params(cfg)
        paths = ssm.trunk_paths(ep)

        def prefix(params, x, _cfg=cfg):
            return ssm.trunk(_cfg, params, x)

        def suffix(params, feats, _cfg=cfg):
            return ssm.head(_cfg, params, feats)

        if cfg.tie_embeddings:
            return PrefixSplit(prefix, suffix, paths)

        def bank_suffix(bank_params, feats, _cfg=cfg):
            return ssm.bank_head(_cfg, bank_params, feats)

        return PrefixSplit(prefix, suffix, paths,
                           suffix_paths=ssm.head_paths(ep),
                           bank_suffix=bank_suffix)

    def _build_decode_split(self, cfg) -> DecodeSplit:
        sp = self.split(cfg)

        def trunk_step(params, pool, tables, lengths, tokens, _cfg=cfg):
            return ssm.paged_trunk_step(
                _cfg, params, pool, tables, lengths, tokens)

        def head_fn(params, hidden, _cfg=cfg):
            return ssm.head(_cfg, params, hidden)

        def step(params, pool, tables, lengths, tokens, _cfg=cfg):
            return ssm.paged_decode_step(
                _cfg, params, pool, tables, lengths, tokens)

        def step_unpaged(params, cache, tokens, _cfg=cfg):
            return ssm.decode_step(_cfg, params, cache, tokens)

        def init_pool(num_pages, page_size, _cfg=cfg):
            return ssm.init_state_pool(_cfg, num_pages, page_size)

        def init_cache(batch, max_len, _cfg=cfg):
            return ssm.init_cache(_cfg, batch, max_len)

        bank = None
        if sp.bank_suffix is not None:
            def bank(bank_params, hidden, _cfg=cfg):
                return ssm.bank_head(_cfg, bank_params, hidden)

        def prefill_chunk(params, pool, tables, lengths, tokens, _cfg=cfg):
            return ssm.paged_prefill_chunk(
                _cfg, params, pool, tables, lengths, tokens)

        return DecodeSplit(trunk_step, head_fn, step, step_unpaged,
                           init_pool, init_cache, sp.prefix_paths,
                           head_paths=sp.suffix_paths,
                           head_signature=sp.suffix_signature,
                           bank_head=bank,
                           prefill_chunk=prefill_chunk)


class GriffinAdapter(_TokenLMAdapter):
    """Griffin recurrent/local-attention hybrids, full merge-and-serve tier
    (ISSUE 10): the RG-LRU runs through ``kernels.ops.rg_lru_scan`` and the
    local attention through ``ops.flash_attention(window=...)``.  Streaming
    decode carries a ring-buffer KV of ``window`` slots per attention layer
    plus the recurrent ``(h, conv)`` state."""

    name = "hybrid"
    family = "hybrid"

    def default_config(self):
        return griffin.GriffinConfig(
            name="tiny-griffin", n_layers=3, pattern=("rec", "rec", "attn"),
            d_model=32, d_rnn=32, n_heads=2, n_kv_heads=1, head_dim=16,
            d_ff=64, vocab_size=64, vocab_multiple=32, window=8,
            tie_embeddings=False, scan_layers=False, chunk=16,
        )

    def init(self, cfg, key):
        return griffin.init(cfg, key)

    def forward(self, cfg, params, x):
        return griffin.head(cfg, params, griffin.trunk(cfg, params, x))

    def loss(self, cfg, params, batch):
        return griffin.loss_fn(cfg, params, batch)

    def layer_activations(self, cfg, params, batch: dict) -> dict:
        return griffin.layer_activations(cfg, params, batch["tokens"])

    def _build_split(self, cfg) -> PrefixSplit:
        ep = self.eval_params(cfg)
        paths = griffin.trunk_paths(ep)

        def prefix(params, x, _cfg=cfg):
            return griffin.trunk(_cfg, params, x)

        def suffix(params, feats, _cfg=cfg):
            return griffin.head(_cfg, params, feats)

        if cfg.tie_embeddings:
            return PrefixSplit(prefix, suffix, paths)

        def bank_suffix(bank_params, feats, _cfg=cfg):
            return griffin.bank_head(_cfg, bank_params, feats)

        return PrefixSplit(prefix, suffix, paths,
                           suffix_paths=griffin.head_paths(ep),
                           bank_suffix=bank_suffix)

    def _build_decode_split(self, cfg) -> DecodeSplit:
        sp = self.split(cfg)

        def trunk_step(params, pool, tables, lengths, tokens, _cfg=cfg):
            return griffin.paged_trunk_step(
                _cfg, params, pool, tables, lengths, tokens)

        def head_fn(params, hidden, _cfg=cfg):
            return griffin.head(_cfg, params, hidden)

        def step(params, pool, tables, lengths, tokens, _cfg=cfg):
            return griffin.paged_decode_step(
                _cfg, params, pool, tables, lengths, tokens)

        def step_unpaged(params, cache, tokens, _cfg=cfg):
            return griffin.decode_step(_cfg, params, cache, tokens)

        def init_pool(num_pages, page_size, _cfg=cfg):
            return griffin.init_state_pool(_cfg, num_pages, page_size)

        def init_cache(batch, max_len, _cfg=cfg):
            # the paged pool rings exactly `window` KV slots per request, the
            # unpaged cache min(window, max_len) — bitwise replay parity
            # (serving.decode.verify_bitwise) therefore needs the full ring
            if _cfg.window > max_len:
                raise ValueError(
                    f"hybrid: streaming decode needs window <= max_len "
                    f"(window={_cfg.window}, max_len={max_len})")
            return griffin.init_cache(_cfg, batch, max_len)

        bank = None
        if sp.bank_suffix is not None:
            def bank(bank_params, hidden, _cfg=cfg):
                return griffin.bank_head(_cfg, bank_params, hidden)

        def prefill_chunk(params, pool, tables, lengths, tokens, _cfg=cfg):
            return griffin.paged_prefill_chunk(
                _cfg, params, pool, tables, lengths, tokens)

        return DecodeSplit(trunk_step, head_fn, step, step_unpaged,
                           init_pool, init_cache, sp.prefix_paths,
                           head_paths=sp.suffix_paths,
                           head_signature=sp.suffix_signature,
                           bank_head=bank,
                           prefill_chunk=prefill_chunk)


class MoEAdapter(_TokenLMAdapter):
    """Mixture-of-experts LMs, full merge-and-serve tier (ISSUE 10).  The
    serving surfaces discard the router aux-loss (``forward`` here returns
    logits only; ``loss`` recomputes the aux term through the family loss).
    Streaming decode rebinds ``group_size=1`` so routing is per-token
    independent — each token is its own capacity group and can never be
    dropped, which is what makes paged and unpaged decode bitwise equal."""

    name = "moe"
    family = "moe"

    def default_config(self):
        return moe.MoELMConfig(
            name="tiny-moe", n_layers=2, d_model=32, n_heads=2, n_kv_heads=2,
            head_dim=16, vocab_size=64, vocab_multiple=32, n_experts=4,
            top_k=2, n_shared_experts=1, d_ff_expert=16, d_ff_dense=64,
            first_dense_layers=0, group_size=1, tie_embeddings=False,
            scan_layers=False,
        )

    def init(self, cfg, key):
        return moe.init(cfg, key)

    def forward(self, cfg, params, x):
        return moe.head(cfg, params, moe.trunk(cfg, params, x))

    def loss(self, cfg, params, batch):
        return moe.loss_fn(cfg, params, batch)

    def layer_activations(self, cfg, params, batch: dict) -> dict:
        return moe.layer_activations(cfg, params, batch["tokens"])

    def _build_split(self, cfg) -> PrefixSplit:
        ep = self.eval_params(cfg)
        paths = moe.trunk_paths(ep)

        def prefix(params, x, _cfg=cfg):
            return moe.trunk(_cfg, params, x)

        def suffix(params, feats, _cfg=cfg):
            return moe.head(_cfg, params, feats)

        if cfg.tie_embeddings:
            return PrefixSplit(prefix, suffix, paths)

        def bank_suffix(bank_params, feats, _cfg=cfg):
            return moe.bank_head(_cfg, bank_params, feats)

        return PrefixSplit(prefix, suffix, paths,
                           suffix_paths=moe.head_paths(ep),
                           bank_suffix=bank_suffix)

    def _build_decode_split(self, cfg) -> DecodeSplit:
        sp = self.split(cfg)
        # per-token-independent routing for decode (see class docstring)
        dcfg = dataclasses.replace(cfg, group_size=1)

        def trunk_step(params, pool, tables, lengths, tokens, _cfg=dcfg):
            return moe.paged_trunk_step(
                _cfg, params, pool, tables, lengths, tokens)

        def head_fn(params, hidden, _cfg=dcfg):
            return moe.head(_cfg, params, hidden)

        def step(params, pool, tables, lengths, tokens, _cfg=dcfg):
            return moe.paged_decode_step(
                _cfg, params, pool, tables, lengths, tokens)

        def step_unpaged(params, cache, tokens, _cfg=dcfg):
            return moe.decode_step(_cfg, params, cache, tokens)

        def init_pool(num_pages, page_size, _cfg=dcfg):
            return moe.init_kv_pool(_cfg, num_pages, page_size)

        def init_cache(batch, max_len, _cfg=dcfg):
            return moe.init_cache(_cfg, batch, max_len)

        bank = None
        if sp.bank_suffix is not None:
            def bank(bank_params, hidden, _cfg=dcfg):
                return moe.bank_head(_cfg, bank_params, hidden)

        def prefill_chunk(params, pool, tables, lengths, tokens, _cfg=dcfg):
            return moe.paged_prefill_chunk(
                _cfg, params, pool, tables, lengths, tokens)

        return DecodeSplit(trunk_step, head_fn, step, step_unpaged,
                           init_pool, init_cache, sp.prefix_paths,
                           head_paths=sp.suffix_paths,
                           head_signature=sp.suffix_signature,
                           bank_head=bank,
                           prefill_chunk=prefill_chunk)


class FamilyAdapter(MergeableAdapter):
    """Records-only adapter over a :class:`ModelFamily`: any zoo family
    merges (shared records path over params or ``eval_shape`` trees);
    calibration taps and serving splits need a family-specific adapter."""

    def __init__(self, fam: ModelFamily):
        super().__init__()
        self.fam = fam
        self.name = fam.name
        self.family = fam.name

    def default_config(self):
        return self.fam.config_cls()

    def init(self, cfg, key):
        return self.fam.init(cfg, key)

    def forward(self, cfg, params, x):
        return self.fam.forward(cfg, params, x)

    def loss(self, cfg, params, batch):
        return self.fam.loss(cfg, params, batch)

    def forward_batch(self, cfg, params, batch: dict):
        # family-specific batch layouts (module docstring) so the default
        # accuracy tier covers the records-only families too
        if self.name == "vlm":
            logits = self.fam.forward(
                cfg, params, batch["tokens"], batch["patch_embeds"])
            return logits[:, batch["patch_embeds"].shape[1]:, :]
        if self.name == "encdec":
            return self.fam.forward(
                cfg, params, batch["src_embeds"], batch["tokens"])
        out = self.fam.forward(cfg, params, batch["tokens"])
        return out[0] if isinstance(out, tuple) else out


# ---------------------------------------------------------------------------
# Adapter registry
# ---------------------------------------------------------------------------

ADAPTERS: dict[str, MergeableAdapter] = {}


def register_adapter(adapter: MergeableAdapter) -> MergeableAdapter:
    ADAPTERS[adapter.name] = adapter
    return adapter


def get_adapter(name: str) -> MergeableAdapter:
    return ADAPTERS[name]


def adapter_names() -> list:
    return sorted(ADAPTERS)


register_adapter(SmallCNNAdapter())
register_adapter(DenseLMAdapter())
register_adapter(MoEAdapter())
register_adapter(SSMAdapter())
register_adapter(GriffinAdapter())
for _name in ("vlm", "encdec"):
    register_adapter(FamilyAdapter(FAMILIES[_name]))
