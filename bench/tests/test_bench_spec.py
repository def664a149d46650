"""BENCHMARK.json and the files it names: found by name, parsed, and
within the contract's limits on names, units and metric wiring."""
import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH.parent / "src"), str(BENCH)]

from harness import peaks, spec  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_files_found_by_name(workload):
    cell = spec.load_cell(workload, BENCHMARK)
    assert cell.chips in (1, 4)
    assert set(cell.limits["limits"]) <= {"loss_gap", "grad_gap",
                                          "grad_gap_median", "param_gap"}
    ref = cell.reference()
    assert callable(ref.sample_loss) and callable(ref.flops_per_token)
    for key in ("n_workers", "samples_per_worker", "seq_len", "tau",
                "delay_process", "n_microbatches"):
        assert key in cell.traffic


@pytest.mark.parametrize("config", BENCHMARK["configs"],
                         ids=lambda c: c["name"])
def test_config_file_states_its_cut(config):
    data = spec.load_json(BENCH.parent / config["file"])
    assert data["name"] == config["name"]
    assert data["source"].startswith("https://")
    assert data["reduced"] == config["reduced"]
    for key in data["reduced"]:
        assert key in data and not key.endswith(("_dim", "_rank"))
    assert data["deployment"] and isinstance(data["departures"], list)
    assert any(w["config"] == config["name"]
               for w in BENCHMARK["workloads"])


@pytest.mark.parametrize("metric", BENCHMARK["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_reader_found_by_name(metric):
    assert callable(spec.metric_reader(metric["name"]))


def test_names_and_units_use_allowed_characters():
    names = ([w["name"] for w in BENCHMARK["workloads"]]
             + [w["config"] for w in BENCHMARK["workloads"]]
             + [w["traffic"] for w in BENCHMARK["workloads"]]
             + [c["name"] for c in BENCHMARK["configs"]]
             + [k for c in BENCHMARK["configs"] for k in c["reduced"]]
             + [m["name"] for m in METRICS])
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    assert len(set(WORKLOADS)) == len(WORKLOADS)


def test_entries_hold_only_the_contract_keys():
    assert set(BENCHMARK) == {"command", "paths", "run_seconds", "configs",
                              "workloads", "end_to_end", "per_layer"}
    assert all(set(c) == {"name", "source", "file", "reduced", "why"}
               and 1 <= len(c["why"]) <= 200 for c in BENCHMARK["configs"])
    assert all(set(w) == {"name", "config", "traffic", "chips", "why"}
               and 1 <= len(w["why"]) <= 200 for w in BENCHMARK["workloads"])
    e2e = {"name", "unit", "better", "bound", "source"}
    assert all(set(m) - {"workloads"} == e2e and 0.01 <= m["bound"] <= 0.25
               for m in BENCHMARK["end_to_end"])
    per_layer = {"name", "unit", "better", "source", "layer", "moves"}
    assert all(set(m) - {"workloads"} == per_layer
               for m in BENCHMARK["per_layer"])
    assert all(m["better"] in ("lower", "higher") for m in METRICS)


def test_every_moved_metric_is_reported_by_its_cells():
    e2e = {m["name"]: m for m in BENCHMARK["end_to_end"]}
    for m in BENCHMARK["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", WORKLOADS):
            assert w in WORKLOADS
            assert w in moved.get("workloads", WORKLOADS), (m["name"], w)


def test_every_cell_reports_setup_and_another_metric():
    for w in WORKLOADS:
        cell = spec.load_cell(w, BENCHMARK)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer


def test_peaks_refuse_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_traffic_key_reaches_the_run(workload):
    from harness import compare, drive
    cell = spec.load_cell(workload, BENCHMARK)
    compare.check_traffic(cell.traffic)
    flags, fields = drive.launcher_args(cell.traffic, "qwen1.5-0.5b", 7)
    for key, value in cell.traffic.items():
        if key in fields:
            continue
        opt = "--" + key.replace("_", "-")
        assert flags[flags.index(opt) + 1] == str(value), key
    from repro.launch import train as launcher
    _, rc, _ = launcher.build_run(launcher.build_parser().parse_args(flags))
    rc = drive.with_fields(rc, fields)
    for key, value in fields.items():
        obj = rc
        for part in key.split("."):
            obj = getattr(obj, part)
        assert obj == value, key


@pytest.mark.parametrize("traffic", [{"no_such_knob": 1},
                                     {"ambdg.no_such_knob": 1},
                                     {"steps": 5}],
                         ids=["unknown", "unknown-field", "harness-flag"])
def test_a_traffic_key_the_launcher_lacks_is_refused(traffic):
    from harness import drive
    from repro.launch import train as launcher
    with pytest.raises((KeyError, ValueError)):
        flags, fields = drive.launcher_args(traffic, "qwen1.5-0.5b", 7)
        _, rc, _ = launcher.build_run(
            launcher.build_parser().parse_args(flags))
        drive.with_fields(rc, fields)


@pytest.mark.parametrize("traffic", [{"elastic_process": "churn"},
                                     {"strategy": "kbatch"},
                                     {"mesh.n_pods": 4}],
                         ids=["elastic", "strategy", "mesh"])
def test_reference_refuses_traffic_it_does_not_follow(traffic):
    from harness import compare
    with pytest.raises(ValueError):
        compare.check_traffic(traffic)
