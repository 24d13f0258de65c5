"""BENCHMARK.json resolves: every cell finds its configuration, traffic mix
and per-layer readers by name, and the file keeps to its own rules."""
import json
import re

import pytest

from bench import harness, model

SPEC = harness.load_spec()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_resolves_by_name(cell):
    c = harness.resolve(SPEC, cell)
    assert c.config["name"] == next(w["config"] for w in SPEC["workloads"]
                                    if w["name"] == cell)
    assert callable(harness.driver(c.mix["kind"]).drive)
    assert c.metrics, "every cell reports a per-layer metric"
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    for name, reader in c.metrics.items():
        assert callable(reader.read) and reader.UNIT
    m = model.from_config(c.config)
    assert m.variants == 4 and m.dtype_name == "bfloat16"
    assert "logit_err" in c.limits and set(c.limits) <= {"logit_err", "token_gap"}
    assert all(v > 0 for v in c.limits.values())


def test_names_units_and_moves():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert all(NAME.match(n) for n in names) and len(names) == len(set(names))
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    cells = {w["name"] for w in SPEC["workloads"]}

    def reports(metric):
        return set(metric.get("workloads", cells))

    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert reports(m) <= reports(e2e[m["moves"]]), m["name"]
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    assert e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())


def test_reduced_keys_are_in_the_config_file():
    for c in SPEC["configs"]:
        cfg = json.loads((harness.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"].startswith(c["source"])
        assert set(c["reduced"]) <= set(cfg) and c["reduced"] == cfg["reduced"]


def test_unknown_device_kind_raises():
    assert harness.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        harness.peaks("TPU v99")
