"""BENCHMARK.json keeps to its contract, and a new configuration, traffic
mix or metric is picked up from new files alone."""

import hashlib
import json
import os
import re

import pytest

from bench_tiny import BENCH, ROOT, copy_bench, harness, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCHMARK = harness.load_benchmark()
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]


def test_top_level_keys():
    assert list(BENCHMARK) == ["command", "paths", "run_seconds", "configs",
                               "workloads", "end_to_end", "per_layer"]
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert BENCHMARK["paths"] == ["bench"]
    assert isinstance(BENCHMARK["run_seconds"], int)
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = [e["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for e in BENCHMARK[k]]
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cfg", BENCHMARK["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and all(NAME.match(k) for k in cfg["reduced"])
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    with open(os.path.join(ROOT, cfg["file"])) as f:
        data = json.load(f)
    assert set(cfg["reduced"]) <= set(data)
    assert data["reduced"] == cfg["reduced"]
    assert os.path.exists(os.path.join(BENCH, "configs", data["module"] + ".py"))
    assert any(c["config"] == cfg["name"] for c in BENCHMARK["workloads"])


@pytest.mark.parametrize("cell", BENCHMARK["workloads"], ids=lambda c: c["name"])
def test_cell(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in BENCHMARK["configs"]}
    traffic = harness.load_json(BENCH, "traffic", cell["traffic"])
    assert {"io_freq", "warmup_steps"} <= set(traffic)
    e2e = [m["name"] for m in harness.metrics_for(BENCHMARK, cell["name"], False)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(BENCHMARK, cell["name"], True)


def test_at_most_half_the_cells_take_four_chips():
    four = sum(c["chips"] == 4 for c in BENCHMARK["workloads"])
    assert four <= max(1, len(BENCHMARK["workloads"]) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert callable(harness.load_module(BENCH, "metrics", metric["name"]).read)
    cells = {c["name"] for c in BENCHMARK["workloads"]}
    assert set(metric.get("workloads", [])) <= cells
    if metric in BENCHMARK["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["source"] in ("device_trace", "program_span",
                                    "program_counter", "host_clock")
        moved = [m for m in BENCHMARK["end_to_end"] if m["name"] == metric["moves"]]
        assert moved and "\n" not in metric["layer"]
        for cell in metric.get("workloads", cells):
            assert cell in moved[0].get("workloads", [cell])


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            with open(os.path.join(d, f), "rb") as fh:
                out[os.path.join(d, f)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_new_pieces_are_found_by_name(tmp_path):
    bench_dir, benchmark = copy_bench(str(tmp_path))
    before = _digests(bench_dir)
    with open(os.path.join(bench_dir, "traffic", "some2.json"), "w") as f:
        json.dump({"io_freq": 2, "warmup_steps": 2}, f)
    with open(os.path.join(bench_dir, "configs", "nyx_reeber_3.json"), "w") as f:
        json.dump({"module": "nyx_reeber", "shape": [12, 8, 8], "nyx_nprocs": 8,
                   "reeber_nprocs": 1, "consumer_instances": 3}, f)
    with open(os.path.join(bench_dir, "metrics", "steps_in_window.py"), "w") as f:
        f.write("def read(r):\n    return float(len(r.window_steps()))\n")
    with open(benchmark) as f:
        b = json.load(f)
    b["workloads"].append({"name": "cosmo3_some2", "config": "nyx_reeber_3",
                           "traffic": "some2", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                           "better": "higher", "source": "host_clock",
                           "layer": "harness", "moves": "step_ms",
                           "workloads": ["cosmo3_some2"]})
    with open(benchmark, "w") as f:
        json.dump(b, f)
    assert all(_digests(bench_dir)[p] == h for p, h in before.items())
    res = run((bench_dir, benchmark), "cosmo3_some2", trace=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["steps_in_window"]["value"] >= 1
    assert res["metrics"]["steps_in_window"]["unit"] == "steps"
