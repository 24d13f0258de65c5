"""Chip smoke run: GEMEL's main path on a TPU at full StableLM-1.6B width.

    python chip_smoke.py              # one chip: merge, serve, stream-decode
    python chip_smoke.py --chips 4    # four chips: the mesh-sharded suffix bank

One chip.  Two fine-tuned variants of StableLM-2-1.6B
(``repro.configs.stablelm_1_6b``: 24 layers, d=2048, 32 heads of 64,
d_ff=5632, vocab 100352, bf16, ``scan_layers=False``; random weights from
``--seed``) share a base trunk and carry divergent heads.  Their trunks are
merged into one set of buffers, the heads stay private.  The merged pair
then serves 128-token scoring requests through ``MergeAwareEngine`` with the
suffix bank, and stream-decodes through ``StreamingDecoder`` over the paged
KV pool.  Every served logit row is compared with a float32 reference
forward of that variant's own (merged) weights, every decoded token's logits
with a teacher-forced unpaged replay, and the Pallas kernels must have run.

Four chips (``--chips 4``).  Four variants, two layers deep, merged; the
group is served once on one device and once with its suffix bank sharded
over a (1, 4) mesh of ``jax.devices()``, and the two must agree.

Numbers go to stdout line by line; the last line is one JSON object naming
the device.  A failed check exits non-zero before that line is printed.  The
script refuses to run (non-zero exit, no result line) off a TPU, or with
``REPRO_KERNEL_MODE`` set to anything but ``kernel``: a run that measured
the jnp oracles or the interpreter would say nothing about the chip.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
BUCKETS = (4,)  # one batch shape: every compile below happens once
PROMPT_LEN = 128
SCORE_PER_VARIANT = 3  # 6 requests -> micro-batches of 4 and 2 (padded)
DECODE = dict(prompt_len=32, new_tokens=16, page_size=16, max_len=64,
              per_variant=2)
DEADLINE_S = 3600.0  # far beyond the run: no request may expire
TRUNK_NUDGE = 0.01  # fine-tune drift of the trunk, x each leaf's RMS
HEAD_NUDGE = 1.0  # divergent heads
KERNELS = ("flash_attention", "decode_attention", "page_gather",
           "bank_matmul")
# Logit tolerances as fractions of the reference logits' RMS.  The f32
# reference upcasts the very bf16 weights the chip serves, so the whole gap
# is bf16 rounding of activations: unit roundoff 2^-9 at ~10 roundings a
# layer over 24 layers random-walks to ~3e-2.  The same architecture at
# depth 24 in bf16 on CPU measured an RMS error of 1.5e-2 and a max of
# 8.7e-2 against this reference (logit RMS 1.0); the bounds sit ~3x above.
# An fp8 (e4m3, roundoff 2^-4) activation path would land near 0.25 RMS.
RMS_TOL = 0.05
MAX_TOL = 0.25


def _say(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def _refuse(why: str) -> None:
    print(f"chip_smoke: refusing to run: {why}", file=sys.stderr)
    raise SystemExit(2)


# -- model, variants, merge ---------------------------------------------------


def full_config(n_layers=None):
    from repro.configs import stablelm_1_6b

    cfg = dataclasses.replace(stablelm_1_6b.full_config(), scan_layers=False)
    return cfg if n_layers is None else dataclasses.replace(cfg, n_layers=n_layers)


def _nudge():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def nudge(leaf, key, scale):
        x = leaf.astype(jnp.float32)
        rms = jnp.sqrt(jnp.mean(x * x))
        return (x + scale * rms * jax.random.normal(key, x.shape)).astype(leaf.dtype)

    return nudge


def build_store(adapter, cfg, seed: int, mids: tuple):
    """Variants of one base, stored and trunk-merged.  Returns (store,
    unmerged resident bytes).  The variant pytrees are locals, so once the
    merge drops the non-donor trunk buffers nothing else holds them."""
    import jax

    from repro.core import ParamStore, enumerate_groups
    from repro.utils.tree import flatten_paths, unflatten_paths

    nudge = _nudge()
    base = adapter.init(cfg, jax.random.PRNGKey(seed))
    trunk = adapter.split(cfg).prefix_paths
    models = {mids[0]: base}
    flat = sorted(flatten_paths(base).items())
    for i, mid in enumerate(mids[1:], 1):
        keys = jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), i),
                                len(flat))
        models[mid] = unflatten_paths({
            p: nudge(leaf, k, TRUNK_NUDGE if p in trunk else HEAD_NUDGE)
            for (p, leaf), k in zip(flat, keys)})
    del base, flat
    store = ParamStore.from_models(models)
    unmerged = store.resident_bytes()
    shapes = adapter.eval_params(cfg)
    recs = [r for m in models for r in adapter.records(cfg, shapes, m)
            if r.path in trunk]
    models.clear()
    for g in enumerate_groups(recs):
        store.merge_group(g)
    return store, unmerged


def make_engine(store, adapter, cfg, mids, capacity_bytes):
    from repro.serving.costs import costs_for
    from repro.serving.executor import MergeAwareEngine, ModelProgram
    from repro.serving.workload import instances_from_store

    programs = [ModelProgram.from_adapter(adapter, m, cfg=cfg) for m in mids]
    # the LM zoo has no Table-1 cost entry: a stand-in id for scheduler
    # accounting; residency bytes come from the real store buffers.  Every
    # weight is resident on the chip, so there is no transfer to model.
    return MergeAwareEngine(
        store, instances_from_store(store, "tiny-yolo", model_ids=list(mids)),
        programs, capacity_bytes=capacity_bytes,
        costs={"tiny-yolo": costs_for("tiny-yolo")}, buckets=BUCKETS,
        simulate_dma=False, suffix_bank=True)


# -- comparisons --------------------------------------------------------------


def compare(got, ref) -> dict:
    """Errors of ``got`` against ``ref`` (..., V), scaled by the RMS of
    ``ref``, and the share of rows whose top-1 token agrees."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = float(np.sqrt(np.mean(ref * ref)))
    err = np.abs(got - ref)
    return {"max_err": float(err.max()) / scale,
            "rms_err": float(np.sqrt(np.mean(err * err))) / scale,
            "max_abs_err": float(err.max()),
            "top1_agree": float(np.mean(got.argmax(-1) == ref.argmax(-1))),
            "finite": bool(np.isfinite(got).all())}


def merge_errors(parts: list) -> dict:
    return {"max_err": max(p["max_err"] for p in parts),
            "rms_err": max(p["rms_err"] for p in parts),
            "max_abs_err": max(p["max_abs_err"] for p in parts),
            "top1_agree": min(p["top1_agree"] for p in parts),
            "finite": all(p["finite"] for p in parts)}


def within_tolerance(e: dict) -> bool:
    return e["finite"] and e["rms_err"] <= RMS_TOL and e["max_err"] <= MAX_TOL


# -- phases -------------------------------------------------------------------


def scoring_requests(cfg, mids, seed: int, per_variant: int, prompt_len: int):
    """Deadlines interleave the variants, so every micro-batch carries rows
    of both heads and takes the suffix bank."""
    import jax

    from repro.serving.executor import Request

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 100)
    reqs = []
    for j in range(per_variant):
        for i, m in enumerate(mids):
            n = j * len(mids) + i
            toks = jax.random.randint(jax.random.fold_in(key, n),
                                      (1, prompt_len), 0, cfg.vocab_size)
            reqs.append(Request(m, toks, 0.0, DEADLINE_S + n * 1e-3))
    return reqs


def serve_scoring(eng, reqs) -> dict:
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    stats = eng.serve(horizon_s=DEADLINE_S, warmup=reqs[0].payload)
    stats["wall_s"] = time.perf_counter() - t0
    return stats


def check_scoring(store, cfg, completions) -> dict:
    """Every served (S, V) logit row vs the float32 reference forward of
    its variant's current (merged) weights, one variant at a time."""
    import jax.numpy as jnp
    import numpy as np

    from repro.models import reference

    parts = []
    for mid in sorted({c.request.instance_id for c in completions}):
        cs = [c for c in completions if c.request.instance_id == mid]
        toks = jnp.concatenate([c.request.payload for c in cs])
        ref = reference.dense_lm_logits(cfg, store.materialize_cached(mid), toks)
        got = np.stack([np.asarray(c.result) for c in cs])
        parts.append(compare(got, ref))
    return merge_errors(parts)


def decode_requests(cfg, mids, seed: int, prompt_len: int, new_tokens: int,
                    per_variant: int):
    import jax
    import numpy as np

    from repro.serving.decode import DecodeRequest

    key = jax.random.fold_in(jax.random.PRNGKey(seed), 200)
    reqs = []
    for j in range(per_variant):
        for i, m in enumerate(mids):
            toks = jax.random.randint(jax.random.fold_in(key, j * len(mids) + i),
                                      (prompt_len,), 0, cfg.vocab_size)
            reqs.append(DecodeRequest(m, np.asarray(toks), new_tokens))
    return reqs


def serve_decode(eng, reqs, page_size: int, max_len: int) -> dict:
    t0 = time.perf_counter()
    num_pages = len(reqs) * (max_len // page_size)
    stats = eng.serve_decode(
        reqs, horizon_s=DEADLINE_S, page_size=page_size, num_pages=num_pages,
        max_slots=len(reqs), max_len=max_len, record_logits=True)
    stats["wall_s"] = time.perf_counter() - t0
    stats["num_pages"] = num_pages
    return stats


def check_decode(decoder) -> dict:
    """Every decoded token's logits vs a teacher-forced replay of the same
    tokens through the unpaged ``step_unpaged`` cache path."""
    import numpy as np

    from repro.serving.decode import replay_logits

    return merge_errors([compare(np.stack(c.logits), rows)
                         for c, rows in replay_logits(decoder)])


def run_main_path(adapter, cfg, capacity_bytes: int, seed: int = SEED,
                  prompt_len: int = PROMPT_LEN,
                  score_per_variant: int = SCORE_PER_VARIANT,
                  decode: dict = DECODE) -> tuple:
    """Merge two variants, serve and stream-decode them, compare with the
    references.  Returns (figures, failures): every figure the run prints,
    the engine and store, and the failed checks (empty when all passed)."""
    mids = ("A", "B")
    out: dict = {}
    fails: list = []
    t0 = time.perf_counter()
    store, out["unmerged_bytes"] = build_store(adapter, cfg, seed, mids)
    out["merged_bytes"] = store.resident_bytes()
    out["build_s"] = time.perf_counter() - t0
    if not out["merged_bytes"] < out["unmerged_bytes"]:
        fails.append("merged resident bytes not below unmerged")

    eng = make_engine(store, adapter, cfg, mids, capacity_bytes)
    reqs = scoring_requests(cfg, mids, seed, score_per_variant, prompt_len)
    st = serve_scoring(eng, reqs)
    out["serve"] = {k: st[k] for k in ("completed", "microbatches",
                                       "prefix_runs", "suffix_dispatches",
                                       "elapsed_s", "wall_s")}
    if st["completed"] != len(reqs):
        fails.append(f"served {st['completed']} of {len(reqs)} requests")
    if st["suffix_dispatches"] != st["microbatches"]:
        fails.append("a micro-batch skipped the suffix bank")
    t0 = time.perf_counter()
    out["score_vs_f32"] = check_scoring(store, cfg, eng.completions)
    out["score_check_s"] = time.perf_counter() - t0
    if not within_tolerance(out["score_vs_f32"]):
        fails.append("served logits outside tolerance of the f32 reference")

    dreqs = decode_requests(cfg, mids, seed, decode["prompt_len"],
                            decode["new_tokens"], decode["per_variant"])
    st = serve_decode(eng, dreqs, decode["page_size"], decode["max_len"])
    out["decode"] = {k: st[k] for k in ("completed", "lost_in_flight",
                                        "steps", "tokens_decoded",
                                        "trunk_dispatches", "bank_dispatches",
                                        "group_steps", "num_pages",
                                        "elapsed_s", "wall_s")}
    if st["completed"] != len(dreqs) or st["lost_in_flight"]:
        fails.append(f"decoded {st['completed']} of {len(dreqs)} requests")
    if st["bank_dispatches"] != st["group_steps"]:
        fails.append("a decode step skipped the suffix bank")
    t0 = time.perf_counter()
    out["decode_vs_unpaged"] = check_decode(eng.last_decoder)
    out["decode_check_s"] = time.perf_counter() - t0
    if not within_tolerance(out["decode_vs_unpaged"]):
        fails.append("decode logits outside tolerance of the unpaged replay")
    out["engine"], out["store"] = eng, store
    return out, fails


def compiled_programs(adapter, cfg, eng, num_pages: int,
                      prompt_len: int = PROMPT_LEN,
                      decode: dict = DECODE) -> dict:
    """The compiled trunk, suffix bank and paged decode step, at the shapes
    the run used (the jit caches return what the engine compiled)."""
    import jax
    import jax.numpy as jnp

    sp, ds = adapter.split(cfg), adapter.decode_split(cfg)
    group = eng.prefix_groups()[0]
    store = eng.store
    params = store.materialize_cached(group[0])
    b = BUCKETS[-1]
    batch = jnp.zeros((b, prompt_len), jnp.int32)
    feats = jax.ShapeDtypeStruct((b, prompt_len, cfg.d_model), cfg.dtype)
    bank = store.materialize_bank(tuple(group), sp.suffix_paths)
    maxp = decode["max_len"] // decode["page_size"]
    pool = jax.eval_shape(lambda: ds.init_pool(num_pages, decode["page_size"]))
    return {
        "trunk": jax.jit(sp.prefix).lower(params, batch).compile(),
        "bank_head": jax.jit(sp.bank_suffix).lower(bank, feats).compile(),
        "decode_step": jax.jit(ds.trunk_step).lower(
            params, pool, jnp.zeros((b, maxp), jnp.int32),
            jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32)).compile(),
    }


def sharded_bank(adapter, capacity_bytes: int, seed: int) -> list:
    """Four merged variants: the banked micro-batch served on one device,
    then with the bank sharded over a (1, n) mesh; returns the failures."""
    import jax
    import numpy as np
    from jax.sharding import AxisType

    from repro.distributed.partitioning import MeshPlacement
    from repro.distributed.sharding import LogicalRules

    # two layers: the phase exercises the sharded bank, which reads the
    # trunk's output and nothing of its depth
    cfg = full_config(n_layers=2)
    mids = ("A", "B", "C", "D")
    fails = []
    store, unmerged = build_store(adapter, cfg, seed, mids)
    _say("sharded.cut", "n_layers 24 -> 2 (full width)")
    _say("sharded.resident_bytes.unmerged", unmerged)
    _say("sharded.resident_bytes.merged", store.resident_bytes())
    outs = {}
    for lane in ("one_device", "sharded"):
        if lane == "sharded":
            # Auto axes: the engine slices each completion's row out of the
            # sharded bank output eagerly, which explicitly typed axes
            # (make_mesh's default) refuse without an out_sharding
            mesh = jax.make_mesh((1, len(jax.devices())), ("data", "model"),
                                 axis_types=(AxisType.Auto,) * 2)
            store.set_placement(MeshPlacement(LogicalRules(mesh, {}),
                                              bank_axis="model"))
        eng = make_engine(store, adapter, cfg, mids, capacity_bytes)
        st = serve_scoring(eng, scoring_requests(cfg, mids, seed, 1, PROMPT_LEN))
        _say(f"sharded.{lane}", {k: st[k] for k in (
            "completed", "suffix_dispatches", "elapsed_s", "wall_s")})
        if st["completed"] != len(mids) or st["suffix_dispatches"] != 1:
            fails.append(f"{lane}: not one banked micro-batch of {len(mids)}")
        outs[lane] = {c.request.instance_id: np.asarray(c.result)
                      for c in eng.completions}
        del eng
    bank = store.materialize_bank(mids, adapter.split(cfg).suffix_paths)
    w = bank["lm_head"]["w"]
    _say("sharded.bank_sharding", f"{w.sharding.spec} over "
         f"{len(w.sharding.device_set)} devices")
    if len(w.sharding.device_set) != len(jax.devices()):
        fails.append("suffix bank not sharded over every device")
    e = merge_errors([compare(outs["sharded"][m], outs["one_device"][m])
                      for m in mids])
    _say("sharded.vs_one_device", e)
    _say("sharded.bitwise", all(np.array_equal(outs["sharded"][m],
                                               outs["one_device"][m])
                                for m in mids))
    if not within_tolerance(e):
        fails.append("sharded bank outside tolerance of the one-device run")
    return fails


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh-sharded suffix-bank phase")
    ap.add_argument("--seed", type=int, default=SEED)
    args = ap.parse_args(argv)

    env_mode = os.environ.get("REPRO_KERNEL_MODE")
    if env_mode and env_mode != "kernel":
        _refuse(f"REPRO_KERNEL_MODE={env_mode}; unset it (or set kernel)")
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _refuse(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if ops.default_mode() != "kernel":
        _refuse(f"kernel mode resolves to {ops.default_mode()!r}")
    if len(devs) < args.chips:
        _refuse(f"--chips {args.chips} but {len(devs)} devices")

    from repro.models.registry import get_adapter
    from repro.utils.compile_cache import enable_compile_cache

    t_start = time.perf_counter()
    _say("compile_cache", enable_compile_cache())
    _say("device", f"{devs[0].device_kind} x{len(devs)}")
    capacity = devs[0].memory_stats()["bytes_limit"]
    _say("capacity_bytes", capacity)
    adapter = get_adapter("dense")
    ops.reset_dispatch_counts()

    if args.chips == 4:
        fails = sharded_bank(adapter, capacity, args.seed)
    else:
        cfg = full_config()
        _say("config", f"{cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
             f"{cfg.n_heads}x{cfg.head_dim} heads, d_ff={cfg.d_ff}, "
             f"vocab={cfg.padded_vocab}, {jax.numpy.dtype(cfg.dtype).name}")
        out, fails = run_main_path(adapter, cfg, capacity, seed=args.seed)
        for k in ("unmerged_bytes", "merged_bytes", "build_s", "serve",
                  "score_vs_f32", "score_check_s", "decode",
                  "decode_vs_unpaged", "decode_check_s"):
            _say(k, out[k])
        _say("tolerance", {"rms_err": RMS_TOL, "max_err": MAX_TOL})
        counts = ops.dispatch_counts()
        _say("dispatch_counts", counts)
        fails += [f"kernel {k} never dispatched" for k in KERNELS
                  if not counts.get(k)]
        t0 = time.perf_counter()
        progs = compiled_programs(adapter, cfg, out["engine"],
                                  out["decode"]["num_pages"])
        _say("aot_lookup_s", round(time.perf_counter() - t0, 3))
        for name, c in progs.items():
            m = c.memory_analysis()
            _say(f"memory.{name}", {
                f: getattr(m, f + "_size_in_bytes") for f in (
                    "argument", "output", "temp", "generated_code")})
        for name in ("trunk", "decode_step"):
            if "tpu_custom_call" not in progs[name].as_text():
                fails.append(f"compiled {name} holds no Pallas kernel")
        # each phase's wall time minus its steady serving time: compiles
        compile_s = (out["serve"]["wall_s"] - out["serve"]["elapsed_s"]
                     + out["decode"]["wall_s"] - out["decode"]["elapsed_s"])
        _say("compile_s", round(compile_s, 3))
    _say("wall_s", round(time.perf_counter() - t_start, 3))
    _say("peak_bytes_in_use", devs[0].memory_stats()["peak_bytes_in_use"])
    if fails:
        for f in fails:
            print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
        raise SystemExit(1)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
