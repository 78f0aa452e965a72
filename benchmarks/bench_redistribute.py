"""M->N redistribution benchmark (the paper §3.2.2 data-movement lever).

Measures the planned transport path against the whole-file baseline on the
three axes the acceptance criteria name:

* ``mxn``     -- a 4->2 producer/consumer edge: bytes shipped with declared
  consumer ownership (``redistribute: 1``) vs the whole-file payloads the
  pre-plan transport moved, plus the plan-cache hit rate over the run
  (steady-state steps re-plan nothing).
* ``aligned`` -- src and dst decompositions line up: the aligned-boundary
  detector degenerates to CoW views, zero bytes copied and zero shipped.
* ``pack``    -- the JAX executor: a cached plan lowered to
  ``kernels.pack.pack_blocks`` scalar-prefetch DMA tiles (interpret mode on
  CPU) vs the numpy scatter executor, checked to the byte.
* ``pack_nd`` -- a rank-3 reshard on the kernel path: the non-decomposed
  axes flatten onto the 2-D kernels (no numpy fallback), byte-checked;
  ``--smoke`` gates that ``pack_mode`` stays non-None and bytes match.

Every row goes through ``common.emit`` and the whole result dict is persisted
as ``BENCH_redistribute.json`` via ``common.write_json``.

    PYTHONPATH=src python -m benchmarks.bench_redistribute [--smoke]
"""

from __future__ import annotations

import argparse
import shutil
import tempfile
import time
from typing import Any, Dict

import numpy as np

from repro.core import Wilkins, h5, plan_cache, reset_plan_cache
from repro.core.datamodel import (BlockOwnership, reset_transport_stats,
                                  transport_stats)
from repro.core.redistribute import (CompiledPlan, even_blocks,
                                     execute_pack_jax_all)

from .common import Timer, emit, write_json

MIB = 1 << 20


def _mxn_yaml(n_prod: int, n_cons: int, cons_ranks: int,
              redistribute: bool, extra: str = "", spill: bool = False) -> str:
    redist = "redistribute: 1" if redistribute else "redistribute: 0"
    mode = "file: 1, memory: 0" if spill else "memory: 1"
    return f"""
tasks:
  - func: producer
    taskCount: {n_prod}
    nprocs: {n_prod}
    outports:
      - filename: o.h5
        dsets: [{{name: /grid, {mode}}}]
  - func: consumer
    taskCount: {n_cons}
    nprocs: {cons_ranks}
    inports:
      - filename: o.h5
        {redist}
        {extra}
        dsets: [{{name: /grid, {mode}}}]
"""


def _run_mxn(redistribute: bool, mib_per_step: float, steps: int,
             n_prod: int = 4, n_cons: int = 2) -> Dict[str, Any]:
    n = int(mib_per_step * MIB // 8)
    payload = np.arange(n, dtype=np.float64)
    own = BlockOwnership()
    for r, (s, sh) in enumerate(even_blocks((n,), n_prod)):
        own.add(r, s, sh)

    def producer():
        for t in range(steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/grid", data=payload, ownership=own)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            _ = float(f["/grid"][0])  # touch the owned slab

    w = Wilkins(_mxn_yaml(n_prod, n_cons, 2, redistribute),
                {"producer": producer, "consumer": consumer})
    reset_plan_cache()
    reset_transport_stats()
    with Timer() as t:
        rep = w.run(timeout=600)
    s = transport_stats().snapshot()
    pc = plan_cache().snapshot()
    return {
        "redistribute": redistribute,
        "n_prod": n_prod,
        "n_cons": n_cons,
        "steps": steps,
        "mib_per_step": mib_per_step,
        "served": rep.total_served,
        "bytes_shipped": rep.total_bytes_moved,
        "redist_planned_bytes": s["redist_planned_bytes"],
        "redist_shipped_bytes": s["redist_shipped_bytes"],
        "redist_baseline_bytes": s["redist_baseline_bytes"],
        "plan_cache": pc,
        "wall_s": t.dt,
    }


def bench_mxn(mib_per_step: float, steps: int) -> Dict[str, Any]:
    baseline = _run_mxn(False, mib_per_step, steps)
    planned = _run_mxn(True, mib_per_step, steps)
    reduction = baseline["bytes_shipped"] / max(1, planned["bytes_shipped"])
    hit_rate = planned["plan_cache"]["hit_rate"]
    for tag, r in (("whole_file", baseline), ("planned", planned)):
        emit(f"redistribute_mxn_{tag}_bytes_shipped", r["bytes_shipped"], "B",
             f"4->2 edge x {steps}steps x {mib_per_step}MiB")
        emit(f"redistribute_mxn_{tag}_wall", r["wall_s"], "s")
    emit("redistribute_mxn_bytes_reduction", reduction, "x",
         "whole-file bytes shipped / planned bytes shipped (>=2x acceptance)")
    emit("redistribute_plan_cache_hit_rate", hit_rate, "frac",
         ">=0.9 after step 1 acceptance")
    return {"whole_file": baseline, "planned": planned,
            "bytes_reduction_x": reduction,
            "plan_cache_hit_rate": hit_rate}


def bench_aligned(mib_per_step: float, steps: int, nranks: int = 2) -> Dict[str, Any]:
    """src decomposition == dst decomposition: views only, zero bytes copied."""
    n = int(mib_per_step * MIB // 8)
    payload = np.arange(n, dtype=np.float64)
    own = BlockOwnership()
    for r, (s, sh) in enumerate(even_blocks((n,), nranks)):
        own.add(r, s, sh)

    def producer():
        for t in range(steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/grid", data=payload, ownership=own)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            _ = float(f["/grid"][0])

    w = Wilkins(_mxn_yaml(1, 1, nranks, True),
                {"producer": producer, "consumer": consumer})
    reset_plan_cache()
    reset_transport_stats()
    baseline_copies = steps * payload.nbytes  # create_dataset snapshots
    with Timer() as t:
        w.run(timeout=600)
    s = transport_stats().snapshot()
    served = s["redist_aligned"] + s["redist_slabs"]
    ratio = s["redist_aligned"] / max(1, served)
    extra_copied = s["bytes_copied"] - baseline_copies
    emit("redistribute_aligned_ratio", ratio, "frac",
         f"{s['redist_aligned']}/{served} served datasets took the view path")
    emit("redistribute_aligned_bytes_copied", extra_copied, "B",
         "transport-side copies beyond dataset creation (0 acceptance)")
    return {"steps": steps, "mib_per_step": mib_per_step,
            "aligned_served": s["redist_aligned"], "slab_served": s["redist_slabs"],
            "aligned_ratio": ratio, "shipped_bytes": s["redist_shipped_bytes"],
            "transport_bytes_copied": extra_copied, "wall_s": t.dt}


def bench_pack(rows: int, cols: int, n_src: int = 4, n_dst: int = 3,
               iters: int = 5) -> Dict[str, Any]:
    """JAX pack executor vs numpy scatter on one cached plan, byte-checked."""
    import jax.numpy as jnp

    src_boxes = even_blocks((rows, cols), n_src)
    dst_boxes = even_blocks((rows, cols), n_dst)
    plan = CompiledPlan(src_boxes, dst_boxes, (rows, cols), np.float32)
    g = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
    gj = jnp.asarray(g)

    with Timer() as t_np:
        for _ in range(iters):
            outs = plan.execute_global(g)
    packed = [np.asarray(a) for a in execute_pack_jax_all(plan, gj)]
    with Timer() as t_jax:
        for _ in range(iters):
            packed = [np.asarray(a) for a in execute_pack_jax_all(plan, gj)]
    for a, b in zip(outs, packed):
        np.testing.assert_array_equal(a, b)
    emit("redistribute_pack_numpy", t_np.dt / iters, "s",
         f"{rows}x{cols} {n_src}->{n_dst} scatter")
    emit("redistribute_pack_pallas", t_jax.dt / iters, "s",
         "pack_blocks scalar-prefetch DMA (interpret on CPU)")
    return {"rows": rows, "cols": cols, "n_src": n_src, "n_dst": n_dst,
            "numpy_s": t_np.dt / iters, "pallas_s": t_jax.dt / iters,
            "byte_exact": True}


def bench_pack_nd(n0: int, n1: int, n2: int, n_src: int = 4, n_dst: int = 2,
                  axis: int = 1, iters: int = 3) -> Dict[str, Any]:
    """Rank-3 reshard on the kernel path: the plan's non-decomposed axes are
    flattened onto the 2-D pack kernels (no numpy fallback), byte-checked
    against the numpy scatter executor.  This is the volumetric-field case
    (WarpX-class workloads) the 2-D-only lowering used to punt on.
    """
    import jax.numpy as jnp

    shape = (n0, n1, n2)
    src_boxes = even_blocks(shape, n_src, axis=axis)
    dst_boxes = even_blocks(shape, n_dst, axis=axis)
    plan = CompiledPlan(src_boxes, dst_boxes, shape, np.float32)
    g = np.arange(int(np.prod(shape)), dtype=np.float32).reshape(shape)
    gj = jnp.asarray(g)

    with Timer() as t_np:
        for _ in range(iters):
            outs = plan.execute_global(g)
    packed = [np.asarray(a) for a in execute_pack_jax_all(plan, gj)]
    with Timer() as t_jax:
        for _ in range(iters):
            packed = [np.asarray(a) for a in execute_pack_jax_all(plan, gj)]
    # a mismatch must flow into the --smoke gate, not crash the benchmark
    byte_exact = all(np.array_equal(a, b) for a, b in zip(outs, packed))
    emit("redistribute_pack3d_numpy", t_np.dt / iters, "s",
         f"{shape} axis-{axis} {n_src}->{n_dst} scatter")
    emit("redistribute_pack3d_pallas", t_jax.dt / iters, "s",
         f"flattened {plan.pack_mode} lowering (interpret on CPU)")
    return {"shape": list(shape), "axis": axis, "n_src": n_src,
            "n_dst": n_dst, "pack_mode": plan.pack_mode,
            "numpy_s": t_np.dt / iters, "pallas_s": t_jax.dt / iters,
            "byte_exact": byte_exact}


def _run_prefetch(prefetch_on: bool, mib_per_step: float, steps: int,
                  n_prod: int = 4, n_cons: int = 2,
                  compute_iters: int = 3) -> Dict[str, Any]:
    """One 4->2 run with reshard-consuming compute; prefetch on or off.

    Spills through ``file: 1`` so payload preparation writes each slab to
    disk (the serve-side work the executor is supposed to hide); the
    consumer reshards its received slab onto its logical ranks with
    ``TaskComm.reshard`` and computes on each block.
    """
    from repro.core import comm as comm_mod

    n = int(mib_per_step * MIB // 8)
    payload = np.arange(n, dtype=np.float64)
    own = BlockOwnership()
    for r, (s, sh) in enumerate(even_blocks((n,), n_prod)):
        own.add(r, s, sh)

    def producer():
        for t in range(steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/grid", data=payload, ownership=own)

    def consumer(comm):
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            blocks = comm.reshard(f["/grid"])   # slab -> per-rank blocks
            for _ in range(compute_iters):      # consumer compute to overlap
                for b in blocks:
                    _ = np.tanh(b).sum()

    knob = "prefetch: 1" if prefetch_on else "prefetch: 0"
    spill = tempfile.mkdtemp(prefix="wilkins_bench_prefetch_")
    try:
        w = Wilkins(_mxn_yaml(n_prod, n_cons, 2, True, extra=knob, spill=True),
                    {"producer": producer, "consumer": consumer},
                    spill_dir=spill)
        reset_plan_cache()
        reset_transport_stats()
        with Timer() as t:
            rep = w.run(timeout=600)
    finally:
        shutil.rmtree(spill, ignore_errors=True)
    s = transport_stats().snapshot()
    return {
        "prefetch": prefetch_on,
        "steps": steps,
        "mib_per_step": mib_per_step,
        "served": rep.total_served,
        "wall_s": t.dt,
        "prefetch_hits": s["prefetch_hits"],
        "prefetch_misses": s["prefetch_misses"],
        "prepared_s": s["prefetch_prepared_s"],
        "blocked_s": s["prefetch_blocked_s"],
    }


def bench_prefetch(mib_per_step: float, steps: int) -> Dict[str, Any]:
    """Async slab prefetch on the 4->2 edge: how much of the slab-serve time
    hides behind consumer compute (>= 0.30 acceptance)."""
    off = _run_prefetch(False, mib_per_step, steps)
    on = _run_prefetch(True, mib_per_step, steps)
    served = max(1, on["prefetch_hits"] + on["prefetch_misses"])
    hit_rate = on["prefetch_hits"] / served
    overlap = 0.0
    if on["prepared_s"] > 0:
        overlap = 1.0 - on["blocked_s"] / on["prepared_s"]
    emit("redistribute_prefetch_off_wall", off["wall_s"], "s",
         f"4->2 edge x {steps}steps x {mib_per_step}MiB, sync serve")
    emit("redistribute_prefetch_on_wall", on["wall_s"], "s",
         "payload futures on the prefetch executor")
    emit("redistribute_prefetch_hit_rate", hit_rate, "frac",
         "payload ready before the consumer asked")
    emit("redistribute_prefetch_overlap", overlap, "frac",
         "serve time hidden behind consumer compute (>=0.3 acceptance)")
    return {"off": off, "on": on, "hit_rate": hit_rate,
            "overlap_frac": overlap}


def main(smoke: bool = False) -> Dict[str, Any]:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes for CI smoke runs")
    ap.add_argument("--mib", type=float, default=None,
                    help="payload MiB per step for the M->N benchmark")
    args, _ = ap.parse_known_args([]) if smoke else ap.parse_known_args()
    smoke = smoke or args.smoke

    if smoke:
        mib, steps, rows, vol = 2.0, 12, 256, (32, 96, 8)
    else:
        mib, steps, rows, vol = (args.mib or 64.0), 20, 4096, (64, 512, 32)

    results = {
        "config": {"smoke": smoke, "mib_per_step": mib, "steps": steps},
        "mxn": bench_mxn(mib, steps),
        "aligned": bench_aligned(mib, steps),
        "pack": bench_pack(rows, 128),
        "pack_nd": bench_pack_nd(*vol),
        "prefetch": bench_prefetch(mib, steps),
    }
    write_json("redistribute", results)
    return results


if __name__ == "__main__":
    main()
