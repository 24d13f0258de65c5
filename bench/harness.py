"""One run of one benchmark cell: build the merged zoo, drive its traffic
through the program's normal path for a measured window, then check what the
window served against a plain reference.

Everything that belongs to one configuration, model family, traffic mix or
per-layer metric lives in its own file, found by the name ``BENCHMARK.json``
or the configuration gives:

    bench/configs/<config>.json      sizes, zoo, serving knobs
    bench/families/<family>.py       the family's sizes record, weights,
                                     program config and work counts
    bench/references/<family>.py     its plain float32 reference
    bench/traffic/<traffic>.json     parameters of the traffic; its ``kind``
    bench/drivers/<kind>.py          drives that kind through the program
    bench/limits/<cell>.json         the limit of each number the check compares
    bench/metrics/<metric>.py        ``read(ctx)`` -> value or None
    bench/peaks.json                 device peaks, keyed by ``device_kind``

Timeline of a run (all on one host clock, ``time.perf_counter``):

    process start .. window start    set-up: weights, merge, engine, every
                                     shape of this cell's traffic compiled
                                     (the driver's warm-up)
    window                           ``--seconds`` of the driver's traffic
                                     through the program's normal path
    after the window                 HBM peak read; requests open at the
                                     close finish; sampled answers on the
                                     host; program state freed; reference
                                     check
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import types
from collections import defaultdict
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from bench import model, trace, weights

ROOT = Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
DRAIN_S = 120.0  # longest wait for requests still open when the window closes


# -- resolving a cell ---------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    metrics: dict  # per-layer metric name -> reader module
    end_to_end: list  # end-to-end metric names this cell reports
    limits: dict  # compared number -> its limit (bench/limits/<cell>.json)


def load_spec(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration, traffic mix and
    per-layer metric readers loaded from their files."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    (cfg_entry,) = [c for c in spec["configs"] if c["name"] == w["config"]]
    config = json.loads((root / cfg_entry["file"]).read_text())
    mix = json.loads((root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())

    def applies(m):
        return "workloads" not in m or workload in m["workloads"]

    return Cell(workload, w["chips"], config, mix,
                {m["name"]: _reader(m["name"]) for m in spec["per_layer"] if applies(m)},
                [m["name"] for m in spec["end_to_end"] if applies(m)],
                _limits(root / "bench" / "limits" / f"{workload}.json"))


def _limits(path: Path) -> dict:
    """A cell's limits file: compared number -> limit, and under ``why`` (left
    out here) the reasons they were set where they are."""
    raw = json.loads(path.read_text())
    return {k: v for k, v in raw.items() if k != "why"}


def peaks(device_kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"bench/peaks.json has {sorted(table)}")
    return table[device_kind]


def refuse(why: str) -> None:
    print(f"bench: refusing to run: {why}", file=sys.stderr, flush=True)
    raise SystemExit(2)


def open_chip(workload: str) -> Cell:
    """The cell named ``workload``, once the machine is shown fit to run it:
    a TPU with as many chips as the cell asks for, the Pallas kernels in
    ``kernel`` mode (a run of the jnp oracles or the interpreter says
    nothing about the chip) and the device in the peaks table.  Otherwise
    exits with code 2 and prints no result.  JAX's persistent compilation
    cache is set to ``.jax_cache/`` in the checkout, so only a checkout's
    first run of a cell compiles."""
    mode = os.environ.get("REPRO_KERNEL_MODE")
    if mode and mode != "kernel":
        refuse(f"REPRO_KERNEL_MODE={mode}; unset it (or set kernel)")
    cell = resolve(load_spec(), workload)
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        refuse(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if len(devs) < cell.chips:
        refuse(f"{workload} needs {cell.chips} chips, JAX sees {len(devs)}")
    from repro.kernels import ops

    if ops.default_mode() != "kernel":
        refuse(f"kernel mode resolves to {ops.default_mode()!r}")
    try:
        peaks(devs[0].device_kind)
    except KeyError as e:
        refuse(str(e))
    return cell


# -- the program under test ---------------------------------------------------


class Clock:
    """The engine's injected clock (``time.perf_counter``).  ``mark`` is its
    first reading after :meth:`arm`, which is the ``t0`` a ``serve`` call
    measures completion times from."""

    def __init__(self):
        self.mark = None

    def arm(self) -> None:
        self.mark = None

    def __call__(self) -> float:
        t = time.perf_counter()
        if self.mark is None:
            self.mark = t
        return t


@dataclasses.dataclass
class Zoo:
    m: model.Model
    engine: object
    store: object
    mids: tuple
    unmerged_bytes: int
    merged_bytes: int
    banked: bool  # the variants' heads run as one suffix bank


def build_zoo(config: dict, seed: int, clock: Clock, buckets: tuple,
              capacity_bytes: int, adapter=None) -> Zoo:
    """The zoo built merged from the start: one trunk and a private head per
    variant from the seed, stored, and the trunk records merged.  The
    unmerged zoo is never held: before the merge the variants' store keys
    name the same trunk buffers.  ``adapter`` (default: a fresh instance of
    the configuration's adapter, so nothing is traced before) lets one
    process serve several seeds from the same traced programs."""
    from repro.core import ParamStore, enumerate_groups
    from repro.models.registry import get_adapter
    from repro.serving.costs import costs_for
    from repro.serving.executor import MergeAwareEngine, ModelProgram
    from repro.serving.workload import instances_from_store

    m = model.from_config(config)
    prog = config["program"]
    pcfg = model.family(m.family).program_config(m, prog)
    if adapter is None:
        adapter = type(get_adapter(prog["adapter"]))()
    trunk, heads = weights.zoo(m, seed)
    weights.check_layout(weights.variant_params(trunk, heads[0]),
                         adapter.eval_params(pcfg))
    mids = tuple(f"{config['name']}/v{v}" for v in range(m.variants))
    store = ParamStore.from_models({
        mid: weights.variant_params(trunk, heads[v]) for v, mid in enumerate(mids)})
    del trunk, heads
    unmerged = store.resident_bytes()
    split = adapter.split(pcfg)
    prefix = split.prefix_paths
    shapes = adapter.eval_params(pcfg)
    recs = [r for mid in mids for r in adapter.records(pcfg, shapes, mid)
            if r.path in prefix]
    for g in enumerate_groups(recs):
        store.merge_group(g)
    programs = [ModelProgram.from_adapter(adapter, mid, cfg=pcfg) for mid in mids]
    # the LM zoo has no cost-table entry: a stand-in id for the scheduler's
    # accounting; residency bytes come from the store's real buffers, and
    # every weight is resident, so there is no transfer to model
    engine = MergeAwareEngine(
        store, instances_from_store(store, "tiny-yolo", model_ids=list(mids)),
        programs, capacity_bytes=capacity_bytes,
        costs={"tiny-yolo": costs_for("tiny-yolo")}, buckets=buckets,
        simulate_dma=False, suffix_bank=True, clock=clock)
    return Zoo(m, engine, store, mids, unmerged, store.resident_bytes(),
               split.bank_suffix is not None)


# -- compile watch ------------------------------------------------------------

_COMPILES: list = []


def _watch_compiles() -> None:
    if not getattr(_watch_compiles, "on", False):
        def listen(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                _COMPILES.append(time.perf_counter())
        jax.monitoring.register_event_duration_secs_listener(listen)
        _watch_compiles.on = True


def _peak_bytes() -> int:
    """Peak bytes in use on the first device so far (0 where the backend
    keeps no allocator statistics, as on the CPU)."""
    stats = jax.devices()[0].memory_stats()
    return stats["peak_bytes_in_use"] if stats else 0


# -- what a driver hands back ------------------------------------------------


@dataclasses.dataclass
class Answer:
    """One sampled request as the program served it: ``tokens`` (int32)
    the positions fed to the model, ``got`` (float32, on the host) the
    served logits of the last ``len(got)`` of them, and ``served`` the
    tokens the program emitted at those positions (None where it emits
    none, as in scoring)."""
    variant: int
    tokens: np.ndarray
    got: np.ndarray
    served: Optional[np.ndarray] = None


@dataclasses.dataclass
class Window:
    """What one measured window gives the run: its span on the host clock,
    what it reports, the per-layer readers' inputs and the answers the
    check compares."""
    t_window: float  # host clock at the window's start
    window_s: float
    peak_bytes: int  # the device's peak in use at the window's close
    attempted: int
    failed: int  # attempted requests that never completed
    end_to_end: dict  # metric -> (value, unit), the traffic's own
    counters: dict  # the program's counters over the window
    work: dict  # kernel -> [flops, bytes] its calls in the window needed
    useful_flops: float  # model FLOPs of real rows, one head per row
    answers: list  # [Answer], the seeded sample
    readings: dict = dataclasses.field(default_factory=dict)  # more reader inputs
    log: dict = dataclasses.field(default_factory=dict)  # lines for stderr
    exact: dict = dataclasses.field(default_factory=dict)  # counts whose limit is 0


def driver(kind: str):
    """The module ``bench/drivers/<kind>.py``."""
    return importlib.import_module(f"bench.drivers.{kind}")


# -- the check against the plain reference ------------------------------------

PAD = 128  # reference batches are padded to a multiple of this many positions


def _rms(x, axis=None):
    return np.sqrt(np.mean(np.square(x, dtype=np.float64), axis=axis))


def _rows(logits, group: list) -> np.ndarray:
    """The rows of ``logits`` (B, S, V) that line up with each answer's
    ``got``, stacked as (positions, V)."""
    return np.concatenate([logits[j, len(a.tokens) - len(a.got):len(a.tokens)]
                           for j, a in enumerate(group)])


def _gap(r, chosen, scale) -> float:
    """Widest gap by which a chosen token's reference logit lies below the
    reference's best at its position, in units of ``scale``."""
    picked = r[np.arange(len(r)), chosen]
    return float(np.max(r.max(-1) - picked) / scale)


def check(m: model.Model, seed: int, answers: list,
          control: bool = False) -> tuple:
    """Compare what the window served with the plain float32 reference of
    each variant's own weights, rebuilt from the seed, run once over each
    sampled request's positions.  Returns ``(numbers, info,
    control_numbers)``; every number is the worst variant's.

    ``logit_err`` is the widest gap between a served logit and the
    reference's, over every compared position and vocabulary entry, in
    units of the reference logits' RMS over those positions.  Where the
    program emitted tokens, ``token_gap`` is the widest gap by which an
    emitted token's reference logit lies below the reference's best at its
    position, in the same units: greedy decoding emits the best.

    With ``control``, the control's answers take the program's place: the
    reference itself computed in float8 (the step below the
    configuration's bfloat16) over the same positions, compared by the
    same arithmetic; its tokens are the ones float8 puts first."""
    reference = importlib.import_module(f"bench.references.{m.family}")
    trunk, heads = weights.zoo(m, seed)
    by_var = defaultdict(list)
    for a in answers:
        by_var[a.variant].append(a)
    errs, gaps, rmse, top1, cerrs, cgaps = [], [], [], [], [], []
    for v in sorted(by_var):
        group = by_var[v]
        S = -(-max(len(a.tokens) for a in group) // PAD) * PAD
        toks = np.zeros((len(group), S), np.int32)
        for j, a in enumerate(group):
            toks[j, :len(a.tokens)] = a.tokens
        r = _rows(np.asarray(reference.logits(m, trunk, heads[v], toks)), group)
        g = np.concatenate([a.got for a in group]).astype(np.float32)
        scale = _rms(r)
        errs.append(float(np.max(np.abs(g - r)) / scale))
        rmse.append(float(_rms(g - r) / scale))
        top1.append(float(np.mean(g.argmax(-1) == r.argmax(-1))))
        served = [a.served for a in group if a.served is not None]
        if served:
            gaps.append(_gap(r, np.concatenate(served), scale))
        if control:
            c = _rows(np.asarray(reference.logits(m, trunk, heads[v], toks, "fp8")),
                      group)
            cerrs.append(float(np.max(np.abs(c - r)) / scale))
            if served:
                cgaps.append(_gap(r, c.argmax(-1), scale))
    info = {"rms_err": max(rmse, default=math.inf),
            "top1_agree": min(top1, default=0.0),
            "sampled": len(answers), "variants": len(errs)}
    numbers = {"logit_err": max(errs, default=math.inf)}
    ctl = {"logit_err": max(cerrs, default=math.inf)} if control else {}
    if gaps:
        info["served_tokens"] = sum(len(a.served) for a in answers
                                    if a.served is not None)
        numbers["token_gap"] = max(gaps)
        if control:
            ctl["token_gap"] = max(cgaps)
    return numbers, info, ctl


def judge(numbers: dict, limits: dict) -> tuple:
    """``(correct, checks)``: every limited number at or under its limit,
    and each beside it.  A number the run did not produce reads as
    infinite; a number with no limit is a fault of the cell's files."""
    numbers = {**{k: math.inf for k in limits}, **numbers}
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks


# -- tracing ------------------------------------------------------------------


class Tracer:
    """Starts and stops the JAX profiler around the window when asked, into
    a directory under ``TMPDIR`` that :meth:`load` reads and removes."""

    def __init__(self, active: bool):
        self.active = active
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if active else None

    def start(self) -> None:
        if self.active:
            jax.profiler.start_trace(self.dir)

    def stop(self) -> None:
        if self.active:
            jax.profiler.stop_trace()

    def load(self):
        try:
            return trace.load(trace.find_xplane(self.dir))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# -- one run ------------------------------------------------------------------


def _p95(xs) -> float:
    """Nearest-rank 95th percentile."""
    s = sorted(xs)
    return s[max(0, math.ceil(0.95 * len(s)) - 1)]


def say(key: str, value) -> None:
    print(f"{key}: {value}", file=sys.stderr, flush=True)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_process: float, control: bool = False, adapter=None) -> dict:
    """One run of ``cell``; returns the result object.  With ``control``
    it also holds, under ``"control"``, the verdict and the numbers of the
    same check with the control's answers in the program's place."""
    _watch_compiles()
    dev = jax.devices()[0]
    stats0 = dev.memory_stats() or {}
    capacity = stats0.get("bytes_limit", 16 * 2 ** 30)
    cfg, mix = cell.config, cell.mix
    drive = driver(mix["kind"]).drive
    clock = Clock()
    zoo = build_zoo(cfg, seed, clock, tuple(cfg["serving"]["score_buckets"]),
                    capacity, adapter)
    say("resident_bytes.unmerged", zoo.unmerged_bytes)
    say("resident_bytes.merged", zoo.merged_bytes)
    tracer = Tracer(traced)
    win = drive(zoo, mix, seed, seconds, clock, tracer)
    window_compiles = sum(1 for t in _COMPILES
                          if win.t_window <= t <= win.t_window + win.window_s)
    resident = zoo.store.resident_bytes()
    m = zoo.m
    del zoo
    gc.collect()

    setup_s = win.t_window - t_process
    say("setup_s", setup_s)
    say("window_s", win.window_s)
    say("window_compiles", window_compiles)
    say("counters", win.counters)
    for k, v in win.log.items():
        say(k, v)
    e2e = {"hbm_peak_gb": (win.peak_bytes / 1e9, "GB"), "setup_s": (setup_s, "s"),
           **win.end_to_end}
    metrics = {k: {"value": v, "unit": u} for k, (v, u) in e2e.items()
               if k in cell.end_to_end}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": win.peak_bytes}
    result = {"correct": None, "attempted": win.attempted,
              "failed": win.failed, "metrics": metrics, "device": device}

    if traced:
        tr = tracer.load()
        busy = trace.busy_s(tr)
        device.update(busy_s=busy, window_s=tr.window_s)
        ctx = types.SimpleNamespace(
            model=m, window_s=win.window_s, counters=win.counters,
            resident_bytes=resident, trace=tr, busy_s=busy, work=win.work,
            useful_flops=win.useful_flops, peaks=peaks(dev.device_kind),
            **win.readings)
        per = {}
        for name, reader in cell.metrics.items():
            v = reader.read(ctx)
            if v is not None:
                per[name] = {"value": v, "unit": reader.UNIT}
        result["metrics"] = per
        result["breakdown"] = {"device_ops": trace.top_ops(tr),
                               "idle_gaps": trace.idle_gaps(tr)}

    t_check = time.perf_counter()
    nums, info, ctl = check(m, seed, win.answers, control)
    info["seconds"] = time.perf_counter() - t_check
    say("check", info)
    limits = {**cell.limits, **dict.fromkeys(win.exact, 0)}
    ok, checks = judge({**nums, **win.exact}, limits)
    result["correct"] = bool(ok and win.failed == 0
                             and info["variants"] == m.variants)
    if control:
        c_ok, c_checks = judge(ctl, cell.limits)
        result["control"] = {"correct": bool(c_ok), "checks": c_checks}
    for k, v in checks.items():
        say(f"check.{k}", f"{v['value']} (limit {v['limit']})")
    result["checks"] = checks
    return result
