"""Shared by the harness's CPU tests: a copy of the benchmark's pieces with
every configuration shrunk to a size the CPU runs in a moment.

Importing this module puts the checkout and ``src`` on ``sys.path`` and
holds JAX to the CPU; it makes no topology call."""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import faults, harness  # noqa: E402

#: the profiled tail of a traced run, at the tiny size
harness.TRACE_SECONDS = 0.3

#: the CPU's own op lines stand for a device's in a trace recorded here
CPU_LINES = [("/host:CPU", "tf_XLAPjRtCpuClient")]


def shrink(cfg: dict) -> dict:
    cfg = dict(cfg)
    if "shape" in cfg:
        cfg["shape"] = [max(8, s // 32) for s in cfg["shape"]]
    if "points_per_process" in cfg:
        cfg["points_per_process"] = 1000
    return cfg


def copy_bench(dst: str) -> tuple:
    """Copy the pieces into ``dst/bench`` with every configuration shrunk,
    and BENCHMARK.json to ``dst``; returns (bench dir, BENCHMARK.json)."""
    bench_dir = os.path.join(dst, "bench")
    for kind in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, kind), os.path.join(bench_dir, kind),
                        ignore=shutil.ignore_patterns("__pycache__"))
    cdir = os.path.join(bench_dir, "configs")
    for name in os.listdir(cdir):
        if name.endswith(".json"):
            path = os.path.join(cdir, name)
            with open(path) as f:
                cfg = shrink(json.load(f))
            with open(path, "w") as f:
                json.dump(cfg, f)
    benchmark = os.path.join(dst, "BENCHMARK.json")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), benchmark)
    return bench_dir, benchmark


def cells() -> list:
    return [c["name"] for c in harness.load_benchmark()["workloads"]]


def run(tiny: tuple, cell: str, seed: int = 3, seconds: float = 0.25,
        trace: bool = False, fault: str = "none") -> dict:
    import jax

    bench_dir, benchmark = tiny
    with faults.plant(fault):
        return harness.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                                time.monotonic(), bench_dir=bench_dir,
                                benchmark=benchmark, device_lines=CPU_LINES)
