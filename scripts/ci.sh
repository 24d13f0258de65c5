#!/usr/bin/env bash
# Lightweight CI: tier-1 tests + the serving benchmark artifact, on CPU with
# the pure-jnp kernel oracles.  Run from the repo root.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export REPRO_KERNEL_MODE=ref
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# Static invariant gate (DESIGN.md A7): the AST rule engine enforces the
# A-series invariants — layering DAG (subsumes the old vision-import grep,
# now catching aliased/importlib forms too), kernel-dispatch discipline,
# epoch-bump discipline, injected clocks/RNG, tracer hygiene, stable ids —
# with --strict pragma hygiene.  The JSON report is the CI artifact; gate is
# zero unsuppressed findings.
mkdir -p artifacts/analysis
if ! python -m repro.analysis --strict --json > artifacts/analysis/ANALYSIS.json; then
  echo "static analysis failed — findings follow (full report in" \
       "artifacts/analysis/ANALYSIS.json; fix at the cited line or add an" \
       "inline '# repro: allow[RULE-ID] reason' pragma with a justification;" \
       "rule catalog: python -m repro.analysis --list-rules)" >&2
  python -m repro.analysis --strict >&2 || true
  exit 1
fi

# fast lane first: tier-1 feedback without the retraining-heavy slow tests
# (includes tests/test_properties.py — hypothesis property tests that skip
# cleanly when the dependency is absent and run for real when installed),
# then the slow remainder so the full suite still gates the build
python -m pytest -x -q -m "not slow"
python -m pytest -q -m "slow"

# serving engine vs seed path, with the suffix-bank lane (engine-nobank
# comparison row); fails loudly if the artifact can't be built
# (-m so the `benchmarks` package resolves from the repo root)
python -m benchmarks.serve_throughput --json --requests 240 --suffix-bank
# staged-planner search: similarity prefilter vs memory-forward + plan round-trip
python -m benchmarks.plan_search --json
# LM merge-and-serve through the adapter contract (surrogate trainer — the
# real retraining loop is the slow-marked pytest + `--retrain` flag)
python -m benchmarks.lm_merging --json
# drift-adapt lifecycle loop (DESIGN.md L1): breach -> revert -> warm-start
# re-plan -> hot swap under injected drift, with/without-loop timelines
python -m benchmarks.drift_adapt --json
# overload-hardened ingestion front-end (DESIGN.md F1): policy sweep under
# 1-4x overload, cascade objective view, and the deterministic fault sweep
python -m benchmarks.overload --json
# streaming decode serving (DESIGN.md D1): paged KV + continuous batching
# over merged variants vs the per-request decode baseline
python -m benchmarks.decode_serve --json > /dev/null

test -f artifacts/benchmarks/BENCH_serve.json
test -f artifacts/benchmarks/BENCH_plan.json
test -f artifacts/benchmarks/BENCH_lm_serve.json
test -f artifacts/benchmarks/BENCH_drift.json
test -f artifacts/benchmarks/BENCH_overload.json
test -f artifacts/benchmarks/BENCH_decode.json

# suffix-bank acceptance (DESIGN.md S2): exactly ONE suffix dispatch per
# congruent micro-batch, strictly fewer dispatches than the per-member
# fan-out, >=1.5x the per-member engine rps on the merged LM scenario, and
# bitwise-identical outputs in ref mode
python - <<'PY'
import json
s = json.load(open("artifacts/benchmarks/BENCH_serve.json"))["derived"]
assert s["suffix_dispatches"] < s["suffix_runs_nobank"], s
assert s["bank_dispatch_per_microbatch"] == 1.0, s
l = json.load(open("artifacts/benchmarks/BENCH_lm_serve.json"))["derived"]
assert l["outputs_bitwise_identical"], l
assert l["suffix_dispatches"] == l["shared_microbatches"], l
assert l["suffix_dispatches"] < l["suffix_dispatches_nobank"], l
assert l["bank_speedup_rps"] >= 1.5, l
print("suffix-bank acceptance OK")
PY

# drift-adapt acceptance (DESIGN.md L1): breach detected within one sampling
# period, >=1 successful hot swap, finite time-to-recover, post-swap serving
# bitwise vs direct forwards, merged savings restored to >=80% of pre-drift,
# and no request dropped across revert + swap
python - <<'PY'
import json, math
d = json.load(open("artifacts/benchmarks/BENCH_drift.json"))["derived"]
assert d["breach_detect_periods"] <= 1, d
assert d["swaps"] >= 1, d
assert math.isfinite(d["time_to_recover_s"]) and d["time_to_recover_s"] > 0, d
assert d["post_swap_bitwise"], d
assert d["savings_restored_frac"] >= 0.8, d
assert d["all_requests_served"], d
assert d["sim_accuracy_with_loop"] > d["sim_accuracy_no_adapt"], d
print("drift-adapt acceptance OK")
PY

# overload acceptance (DESIGN.md F1): queues stay bounded at their capacity,
# the accounting identity holds (zero lost frames, faults included), degrade
# beats drop-newest on effective accuracy under 2x AND 4x overload, the
# cascade profile never hurts the planner objective, and the injected
# mid-swap failure rolls back atomically (one epoch bump, bindings restored,
# queued requests kept) then re-applies cleanly
python - <<'PY'
import json
o = json.load(open("artifacts/benchmarks/BENCH_overload.json"))["derived"]
assert o["max_depth_all"] <= o["queue_capacity"], o
assert o["lost_total"] == 0, o
assert o["fault_lost_total"] == 0, o
assert o["fault_all_bounded"], o
assert o["degrade_beats_drop_newest_2x"], o
assert o["degrade_beats_drop_newest_4x"], o
assert o["cascade_objective_gain"] >= 0.0, o
assert o["swap_failure_raised"], o
assert o["swap_failure_epoch_bumps"] == 1, o
assert o["swap_failure_bindings_restored"], o
assert o["swap_failure_pending_kept"], o
assert o["swap_reapply_ok"], o
print("overload acceptance OK")
PY

# streaming-decode acceptance (DESIGN.md D1): merged continuous batching
# >=2x the per-request decode baseline in tokens/sec, ref-mode outputs
# BITWISE identical to the unpaged token-by-token decode_step replay,
# exactly ONE shared-trunk and ONE suffix-bank dispatch per decode step for
# the congruent merged group, and a mid-decode plan hot swap that lands with
# exactly one epoch bump and zero lost in-flight requests
python - <<'PY'
import json
d = json.load(open("artifacts/benchmarks/BENCH_decode.json"))["derived"]
assert d["decode_speedup"] >= 2.0, d
assert d["outputs_bitwise_identical"], d
assert d["trunk_dispatch_per_group_step"] == 1.0, d
assert d["bank_dispatch_per_group_step"] == 1.0, d
assert d["swap_epoch_bumps"] == 1, d
assert d["swap_lost_in_flight"] == 0, d
assert d["swap_completed"] == d["requests"], d
assert d["lost_in_flight"] == 0, d
assert d["pool_identity_ok"], d
print("streaming-decode acceptance OK")
PY

# fault-sweep smoke lane with the Pallas kernel bodies actually executing
# (interpret mode): the hardening guarantees must not be ref-mode artifacts
REPRO_KERNEL_MODE=interpret python -m benchmarks.overload --json --faults-only \
  > /dev/null
test -f artifacts/benchmarks/BENCH_overload_faults.json

# decode smoke lane in interpret mode: the Pallas page_gather +
# decode_attention bodies executing on the decode hot path (small trace,
# separate artifact so the ref-mode BENCH_decode is not clobbered; the 2x
# speedup gate is waived here — interpret timing is not meaningful)
REPRO_KERNEL_MODE=interpret python -m benchmarks.decode_serve --json --smoke \
  > /dev/null
test -f artifacts/benchmarks/BENCH_decode_smoke.json

# mixed-family zoo (ISSUE 10): ONE engine serving transformer + ssm +
# griffin + moe variants off one merged store, with the kernels.ops dispatch
# counters watching the hot path (a scan op whose count stays 0 across the
# serving run is the dead-kernel regression this lane pins)
python -m benchmarks.mixed_zoo --json > /dev/null
test -f artifacts/benchmarks/BENCH_mixed_zoo.json

# mixed-zoo smoke lane in interpret mode: the mamba_scan / rg_lru_scan
# Pallas bodies executing inside the promoted ssm/griffin serving paths
# (separate artifact so the ref-mode BENCH_mixed_zoo is not clobbered)
REPRO_KERNEL_MODE=interpret python -m benchmarks.mixed_zoo --json --smoke \
  > /dev/null
test -f artifacts/benchmarks/BENCH_mixed_zoo_smoke.json

# mixed-zoo acceptance (ISSUE 10): all four families served by one engine,
# >=1 committed cross-member group (incl. >=1 spanning families), memory
# saved > 0, merged serving AND streaming decode outputs bitwise vs direct
# forwards in ref and interpret modes, and the scan kernels demonstrably
# dispatched on the serving hot path in both modes
python - <<'PY'
import json
z = json.load(open("artifacts/benchmarks/BENCH_mixed_zoo.json"))["derived"]
assert z["families_served"] == 4, z
assert z["cross_member_groups"] >= 1, z
assert z["cross_family_groups"] >= 1, z
assert z["memory_saved_bytes"] > 0, z
assert z["outputs_bitwise_ref"] and z["outputs_bitwise_interpret"], z
assert z["decode_outputs_bitwise"], z
assert z["dispatch_mamba_scan"] > 0 and z["dispatch_rg_lru_scan"] > 0, z
assert z["dispatch_flash_attention"] > 0, z
assert z["dispatch_mamba_scan_interpret"] > 0, z
assert z["dispatch_rg_lru_scan_interpret"] > 0, z
print("mixed-zoo acceptance OK")
PY

# mesh-sharded serve tier (DESIGN.md S3), forced-8-device CPU lane: the
# ParamStore shard round-trip tests skip on a 1-device host, so this lane
# forces a 2x4 host-platform mesh (the flag lives HERE, not in test code —
# conftest mandate) and then runs the shard_serve benchmark, which builds a
# (devices/4, 4) mesh and fails on a device count four does not divide
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
  python -m pytest -q tests/test_sharded_store.py
XLA_FLAGS="--xla_force_host_platform_device_count=8${XLA_FLAGS:+ $XLA_FLAGS}" \
  python -m benchmarks.shard_serve --json > /dev/null
test -f artifacts/benchmarks/BENCH_shard.json

# delta-compressed plan shipping (DESIGN.md S3 wire format): full vs delta
# vs delta+int8 bytes-on-wire, single-device (no mesh needed)
python -m benchmarks.fig14_bandwidth --json > /dev/null
test -f artifacts/benchmarks/BENCH_plan_wire.json

# sharded-serve acceptance (DESIGN.md S3): sharded decode BITWISE identical
# to single-device in ref AND interpret modes, per-shard epochs advance
# exactly once per shard-affecting event, the bank GEMM actually shard_maps
# over the model axis, and a merged group exceeding one device's budget
# serves to completion under the 2x4 mesh
python - <<'PY'
import json
s = json.load(open("artifacts/benchmarks/BENCH_shard.json"))["derived"]
assert s["sharded"], s  # the forced-8 lane must not degrade
assert s["bitwise_ref"] and s["bitwise_interpret"], s
assert s["epoch_bumps_ok"], s
assert s["apply_plan_epoch_bumps"] == 1, s
assert s["bank_sharded_over_model_axis"], s
assert s["over_budget_served"], s
# weights-only budget strictly below the group's total residency (the
# capacity also carries one micro-batch of activation bytes on every shard)
weights_budget = s["over_budget_capacity_bytes"] - s["over_budget_activation_bytes"]
assert weights_budget < s["group_resident_bytes"], s
assert weights_budget >= s["max_shard_resident_bytes"], s
w = json.load(open("artifacts/benchmarks/BENCH_plan_wire.json"))["derived"]
assert w["wire_ratio_delta_q8"] <= 0.35, w
assert w["wire_ratio_delta"] <= 1.0, w
assert w["unchanged_bitwise"], w
assert w["quant_within_drift"], w
print("sharded-serve + plan-wire acceptance OK")
PY

# kernel-mode matrix: the public ops dispatch layer must match the jnp
# oracles under EVERY CPU-executable REPRO_KERNEL_MODE (ref = oracle pass,
# interpret = kernel bodies executed on CPU), incl. the bank kernel sweeps.
# The abstract contract checker runs first in each lane: signature/shape/
# dtype congruence over the whole OP_TABLE via jax.eval_shape (no device,
# milliseconds), so a skewed kernel fails before the numeric sweep starts.
for mode in ref interpret; do
  REPRO_KERNEL_MODE="$mode" python -m repro.analysis --contracts-only
  REPRO_KERNEL_MODE="$mode" python -m pytest -q tests/test_kernels.py \
    -k "ops_mode or bank_matmul"
done
echo "CI OK"
