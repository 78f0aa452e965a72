"""Shared helpers for the benchmark suite (paper §4 setup, scaled to 1 host).

The paper's synthetic data: per producer process 10^6 grid points (u64) and
10^6 particles (3 x f32) = 19 MiB.  We keep the exact data model and scale
counts so each benchmark finishes in seconds on one CPU; every benchmark
prints ``name,value,unit,derived`` CSV rows so `benchmarks.run` can be diffed
run-over-run.
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, List

import numpy as np

ROWS: List[str] = []


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed place; returns it.

    ``JAX_COMPILATION_CACHE_DIR``, when set, wins and JAX reads it itself.
    Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored): a
    fixed path, since the path is part of the cache key.  Call it before
    the first compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def emit(name: str, value: float, unit: str, derived: str = "") -> None:
    row = f"{name},{value:.6g},{unit},{derived}"
    ROWS.append(row)
    print(row, flush=True)


def write_json(name: str, payload: Dict[str, Any], directory: str = None) -> str:
    """Persist a benchmark's results as ``BENCH_<name>.json`` so the perf
    trajectory is machine-readable run-over-run (``BENCH_DIR`` overrides the
    output directory; defaults to the repo root / cwd)."""
    directory = directory or os.environ.get("BENCH_DIR", ".")
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {path}", flush=True)
    return path


def synthetic_datasets(n_grid: int = 100_000, n_particles: int = 100_000,
                       t: int = 0):
    """The paper's grid (u64 scalars) + particles (3-vec f32) datasets."""
    grid = np.arange(n_grid, dtype=np.uint64) + t
    parts = np.full((n_particles, 3), float(t), np.float32)
    return grid, parts


def total_bytes(n_grid: int, n_particles: int) -> int:
    return n_grid * 8 + n_particles * 12


class Timer:
    def __enter__(self):
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.dt = time.monotonic() - self.t0
        return False
