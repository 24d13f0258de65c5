"""Find the highest rate an open-streams cell sustains: one process builds the
cell's zoo once and offers its traffic at each rate in turn.

    python bench/sweep.py --workload <cell> --rates 40,80,120 --seconds 8

For each rate it prints one JSON line: the offered and completed rates, the
median and 95th-percentile latency from due time, and how far latency grew
from the window's first to its last fifth (a growing backlog).  The cell's
mix keeps the rate chosen from this sweep; the sweep is run once, by hand,
on the chip (``harness.open_chip`` refuses any other machine), and
nothing in a benchmark run calls it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:1] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness
    from bench.drivers import open_streams

    cell = harness.open_chip(args.workload)
    clock = harness.Clock()
    cap = jax.devices()[0].memory_stats()["bytes_limit"]
    zoo = harness.build_zoo(cell.config, args.seed, clock,
                            tuple(cell.config["serving"]["score_buckets"]), cap)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.mix, rate_per_s=rate)
        win = open_streams.drive(zoo, mix, args.seed, args.seconds, clock,
                                 harness.Tracer(False))
        lat = np.asarray(win.readings["latency_s"]) * 1e3
        fifth = max(1, len(lat) // 5)
        batches = max(win.counters["microbatches"], 1)
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "completed_per_s": win.counters["completed"] / win.window_s,
            "p50_ms": float(np.median(lat)), "p95_ms": harness._p95(lat),
            "growth_ms": float(np.median(lat[-fifth:]) - np.median(lat[:fifth])),
            "rows_per_mb": win.counters["completed"] / batches,
            "mixed_share": win.readings["mixed_batches"] / batches,
        }), flush=True)


if __name__ == "__main__":
    main()
