"""M->N redistribution planner/executors: property-based to the byte."""

import json
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.core import Wilkins, h5
from repro.core.channel import Channel
from repro.core.datamodel import (BlockOwnership, File, reset_transport_stats,
                                  transport_stats)
from repro.core.redistribute import (CompiledPlan, PlanCache, RedistSpec,
                                     coalesce_transfers, even_blocks,
                                     execute_pack_jax, execute_pack_jax_all,
                                     gather_to_writers, intersect, plan_cache,
                                     plan_redistribution, redistribute_cached,
                                     redistribute_numpy, reset_plan_cache)


def ragged_blocks(n, nranks, rng, axis=0, shape=None):
    """Random ragged 1-D decomposition along ``axis`` (uneven cut points)."""
    shape = (n,) if shape is None else tuple(shape)
    cuts = sorted(rng.choice(n + 1, size=nranks - 1, replace=True).tolist())
    bounds = [0] + cuts + [n]
    out = []
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        starts = tuple(lo if a == axis else 0 for a in range(len(shape)))
        bshape = tuple(hi - lo if a == axis else s for a, s in enumerate(shape))
        out.append((starts, bshape))
    return out


def test_even_blocks_cover():
    blocks = even_blocks((10, 4), 3)
    assert [b[1][0] for b in blocks] == [4, 3, 3]
    assert blocks[0][0] == (0, 0) and blocks[1][0] == (4, 0)


def test_intersect():
    a = ((0, 0), (4, 4))
    b = ((2, 2), (4, 4))
    assert intersect(a, b) == ((2, 2), (2, 2))
    assert intersect(((0, 0), (2, 2)), ((2, 2), (2, 2))) is None


@settings(max_examples=60, deadline=None)
@given(
    n=st.integers(1, 64),
    cols=st.integers(1, 8),
    m_src=st.integers(1, 7),
    m_dst=st.integers(1, 7),
)
def test_plan_covers_every_dst_cell_once(n, cols, m_src, m_dst):
    """Every destination cell is produced by exactly one transfer (no gaps,
    no overlaps) -- the invariant LowFive's planner must satisfy."""
    src = even_blocks((n, cols), m_src)
    dst = even_blocks((n, cols), m_dst)
    plan = plan_redistribution(src, dst)
    hit = np.zeros((n, cols), dtype=int)
    for t in plan:
        slc = tuple(slice(s, s + k) for s, k in zip(t.global_starts, t.shape))
        hit[slc] += 1
    assert (hit == 1).all()


@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(1, 48),
    cols=st.integers(1, 6),
    m_src=st.integers(1, 6),
    m_dst=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
)
def test_redistribute_preserves_bytes(n, cols, m_src, m_dst, seed):
    """Executing the plan reproduces the exact destination blocks."""
    rng = np.random.default_rng(seed)
    arr = rng.integers(0, 1000, size=(n, cols)).astype(np.int64)
    src = even_blocks(arr.shape, m_src)
    dst = even_blocks(arr.shape, m_dst)
    outs = redistribute_numpy(arr, src, dst)
    for (starts, shape), out in zip(dst, outs):
        slc = tuple(slice(s, s + k) for s, k in zip(starts, shape))
        np.testing.assert_array_equal(out, arr[slc])


def test_gather_to_writers_single():
    """io_proc=1 (LAMMPS): rank 0 owns the full global extent."""
    own = BlockOwnership()
    for r, (starts, shape) in enumerate(even_blocks((32, 3), 8)):
        own.add(r, starts, shape)
    g = gather_to_writers(own, 1)
    assert g.nranks() == 1
    assert g.blocks[0] == ((0, 0), (32, 3))


def test_gather_to_writers_subset():
    own = BlockOwnership()
    for r, (starts, shape) in enumerate(even_blocks((30,), 6)):
        own.add(r, starts, shape)
    g = gather_to_writers(own, 2)
    assert g.nranks() == 2
    total = sum(sh[0] for _, sh in g.blocks.values())
    assert total == 30


def test_reshard_jax_roundtrip():
    import jax
    from repro.core.redistribute import reshard_jax

    x = np.arange(12.0).reshape(3, 4)
    arr = jax.numpy.asarray(x)
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    out = reshard_jax(arr, sh)
    np.testing.assert_array_equal(np.asarray(out), x)


# ---------------------------------------------------------------------------
# multi-axis / ragged planning properties
# ---------------------------------------------------------------------------
@settings(max_examples=40, deadline=None)
@given(
    n=st.integers(2, 48),
    cols=st.integers(2, 12),
    m_src=st.integers(1, 5),
    m_dst=st.integers(1, 5),
)
def test_plan_covers_cross_axis(n, cols, m_src, m_dst):
    """src decomposed along axis 0, dst along axis 1: still exact cover."""
    src = even_blocks((n, cols), m_src, axis=0)
    dst = even_blocks((n, cols), m_dst, axis=1)
    hit = np.zeros((n, cols), dtype=int)
    for t in plan_redistribution(src, dst):
        slc = tuple(slice(s, s + k) for s, k in zip(t.global_starts, t.shape))
        hit[slc] += 1
    assert (hit == 1).all()


def test_plan_covers_cross_axis_seeded():
    """Deterministic cross-axis + ragged cover (runs without hypothesis)."""
    rng = np.random.default_rng(7)
    for n, cols, m_src, m_dst, src_axis, dst_axis in [
        (17, 5, 3, 2, 0, 1), (32, 8, 4, 4, 1, 0), (9, 9, 2, 5, 1, 1)
    ]:
        src = ragged_blocks([n, cols][src_axis], m_src, rng, axis=src_axis,
                            shape=(n, cols))
        dst = even_blocks((n, cols), m_dst, axis=dst_axis)
        hit = np.zeros((n, cols), dtype=int)
        for t in plan_redistribution(src, dst):
            slc = tuple(slice(s, s + k) for s, k in zip(t.global_starts, t.shape))
            hit[slc] += 1
        assert (hit == 1).all(), (n, cols, m_src, m_dst, src_axis, dst_axis)


def test_ragged_ownership_executors_byte_exact():
    """Ragged src x ragged dst: scatter executor == redistribute_numpy."""
    rng = np.random.default_rng(42)
    for trial in range(25):
        n = int(rng.integers(1, 64))
        cols = int(rng.integers(1, 7))
        src = ragged_blocks(n, int(rng.integers(1, 6)), rng, shape=(n, cols))
        dst = ragged_blocks(n, int(rng.integers(1, 6)), rng, shape=(n, cols))
        g = rng.integers(0, 1000, size=(n, cols)).astype(np.int64)
        want = redistribute_numpy(g, src, dst)
        plan = CompiledPlan(src, dst, g.shape, g.dtype)
        got_global = plan.execute_global(g)
        src_blocks = [g[s[0]:s[0] + sh[0]] for (s, sh) in src]
        got_scatter = plan.execute(src_blocks)
        for w, a, b in zip(want, got_global, got_scatter):
            np.testing.assert_array_equal(w, a)
            np.testing.assert_array_equal(w, b)


def test_scatter_executor_writes_into_preallocated_blocks():
    g = np.arange(40.0).reshape(10, 4)
    src = even_blocks(g.shape, 5)
    dst = even_blocks(g.shape, 2)
    plan = CompiledPlan(src, dst, g.shape, g.dtype)
    out = [np.full(sh, -1.0) for (_, sh) in dst]
    res = plan.execute_global(g, out=out)
    assert res[0] is out[0] and res[1] is out[1]  # no reallocation
    np.testing.assert_array_equal(out[0], g[:5])
    np.testing.assert_array_equal(out[1], g[5:])


def test_coalescing_merges_contiguous_runs():
    from repro.core.redistribute import Transfer

    # 4 src blocks feeding 2 dst blocks: per-(src,dst) descriptors stay
    # separate (scatter reads per-source blocks) but the global-buffer runs
    # coalesce across src ranks -- one contiguous copy per dst block.
    src = even_blocks((8, 4), 4)
    dst = even_blocks((8, 4), 2)
    plan = CompiledPlan(src, dst, (8, 4), np.float32)
    assert [len(s) for s in plan.per_dst] == [2, 2]
    assert [len(s) for s in plan.per_dst_runs] == [1, 1]
    assert plan.per_dst_runs[0][0] == Transfer(-1, 0, (0, 0), (4, 4))
    assert plan.per_dst_runs[1][0] == Transfer(-1, 1, (4, 0), (4, 4))
    # same dst fed by two adjacent pieces of one src block merges either way
    parts = [Transfer(0, 0, (0, 0), (2, 4)), Transfer(0, 0, (2, 0), (3, 4))]
    assert coalesce_transfers(parts) == [Transfer(0, 0, (0, 0), (5, 4))]
    # different dst ranks never merge
    apart = [Transfer(0, 0, (0, 0), (2, 4)), Transfer(0, 1, (2, 0), (3, 4))]
    assert len(coalesce_transfers(apart, ignore_src=True)) == 2


def test_aligned_detector():
    src = even_blocks((12, 3), 3)
    assert CompiledPlan(src, src, (12, 3), np.int32).aligned
    assert CompiledPlan(src, src, (12, 3), np.int32).identity
    off = even_blocks((12, 3), 4)
    p = CompiledPlan(src, off, (12, 3), np.int32)
    assert not p.aligned and not p.identity
    # aligned but not identity: dst is a permutation-compatible single-block
    assert CompiledPlan([((0, 0), (12, 3))], [((0, 0), (12, 3))],
                        (12, 3), np.int32).aligned


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------
def test_plan_cache_hit_and_invalidation():
    c = PlanCache(maxsize=8)
    src = even_blocks((16, 2), 4)
    dst = even_blocks((16, 2), 2)
    p1 = c.get(src, dst, (16, 2), np.float64)
    p2 = c.get(src, dst, (16, 2), np.float64)
    assert p1 is p2
    assert c.snapshot()["hits"] == 1 and c.snapshot()["misses"] == 1
    # different dtype / shape / blocks are different plans
    assert c.get(src, dst, (16, 2), np.float32) is not p1
    assert c.get(src, dst[::-1], (16, 2), np.float64) is not p1
    assert c.snapshot()["misses"] == 3


def test_plan_cache_lru_eviction():
    c = PlanCache(maxsize=2)
    shapes = [(8, 1), (9, 1), (10, 1)]
    plans = [c.get(even_blocks(s, 2), even_blocks(s, 2), s, np.int8)
             for s in shapes]
    assert c.snapshot()["evictions"] == 1 and len(c) == 2
    # (8,1) was evicted: re-getting it misses and recompiles
    again = c.get(even_blocks((8, 1), 2), even_blocks((8, 1), 2), (8, 1), np.int8)
    assert again is not plans[0]
    # (10,1) is still hot
    assert c.get(even_blocks((10, 1), 2), even_blocks((10, 1), 2),
                 (10, 1), np.int8) is plans[2]


def test_redistribute_cached_matches_uncached():
    reset_plan_cache()
    g = np.arange(60).reshape(12, 5)
    src = even_blocks(g.shape, 3)
    dst = even_blocks(g.shape, 4)
    for _ in range(3):
        outs = redistribute_cached(g, src, dst)
        for w, a in zip(redistribute_numpy(g, src, dst), outs):
            np.testing.assert_array_equal(w, a)
    snap = plan_cache().snapshot()
    assert snap["hits"] == 2 and snap["misses"] == 1


# ---------------------------------------------------------------------------
# JAX pack executor (kernels/pack.py lowering)
# ---------------------------------------------------------------------------
def test_pack_executor_matches_numpy_scatter(monkeypatch):
    import jax.numpy as jnp

    from repro.kernels import pack

    rng = np.random.default_rng(3)
    default = pack.BLOCK_BYTES
    # a 4 KiB block budget cuts 300 columns into ragged 128-column blocks
    for rows, cols, m_src, m_dst, budget in [
        (64, 8, 4, 2, default), (40, 16, 3, 3, default),
        (37, 8, 2, 5, default), (37, 300, 2, 5, 4096),
    ]:
        monkeypatch.setattr(pack, "BLOCK_BYTES", budget)
        g = rng.normal(size=(rows, cols)).astype(np.float32)
        src = even_blocks(g.shape, m_src)
        dst = even_blocks(g.shape, m_dst)
        plan = CompiledPlan(src, dst, g.shape, g.dtype)
        want = plan.execute_global(g)
        gj = jnp.asarray(g)
        for r in range(m_dst):
            got = np.asarray(execute_pack_jax(plan, r, gj))
            np.testing.assert_array_equal(got, want[r])


def test_pack_tiles_cached_on_plan():
    plan = CompiledPlan(even_blocks((32, 8), 2), even_blocks((32, 8), 4),
                        (32, 8), np.float32)
    t1, s1 = plan.pack_tiles(1, 8)
    t2, s2 = plan.pack_tiles(1, 8)
    assert t1 is t2 and s1 is s2  # lowered once, cached on the plan


# ---------------------------------------------------------------------------
# channel integration: slab shipping, aligned views, spill roundtrip
# ---------------------------------------------------------------------------
def _mxn_yaml(n_prod, n_cons, cons_ranks, extra=""):
    return f"""
tasks:
  - func: producer
    taskCount: {n_prod}
    outports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    taskCount: {n_cons}
    nprocs: {cons_ranks}
    inports:
      - filename: o.h5
        redistribute: 1
        {extra}
        dsets: [{{name: /g, memory: 1}}]
"""


def _owned(n, m):
    own = BlockOwnership()
    for r, (s, sh) in enumerate(even_blocks((n,), m)):
        own.add(r, s, sh)
    return own


def test_mxn_channel_ships_only_owned_slabs():
    n, steps = 512, 3
    got = []
    lock = threading.Lock()

    def producer():
        own = _owned(n, 4)
        for t in range(steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(n, dtype=np.float64) + t,
                                 ownership=own)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            d = f["/g"]
            with lock:
                got.append((tuple(d.attrs["redist_box_starts"]), d.shape,
                            np.asarray(d[:])))

    reset_plan_cache()
    reset_transport_stats()
    w = Wilkins(_mxn_yaml(4, 2, 2), {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    # 4 channels x steps serves, each shipping HALF the dataset
    assert rep.total_served == 4 * steps
    assert rep.total_bytes_moved == 4 * steps * (n // 2) * 8
    s = transport_stats().snapshot()
    assert s["redist_baseline_bytes"] == 2 * s["redist_shipped_bytes"]
    assert plan_cache().snapshot()["misses"] == 1  # one compile for the edge
    for starts, shape, data in got:
        assert shape == (n // 2,)
        base = data[0] - starts[0]  # payload + t offset
        np.testing.assert_array_equal(
            data, np.arange(starts[0], starts[0] + n // 2) + base)


def test_mxn_consumer_gets_per_rank_ownership():
    n = 64
    boxes = []

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(n, dtype=np.float64),
                             ownership=_owned(n, 4))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            boxes.append(dict(f["/g"].ownership.blocks))

    w = Wilkins(_mxn_yaml(1, 1, 2), {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    # nslots=1, nranks=2: the instance owns the whole extent split in two
    assert boxes == [{0: ((0,), (32,)), 1: ((32,), (32,))}]


def test_aligned_decomposition_ships_views_zero_copy():
    n = 256

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.zeros(n), ownership=_owned(n, 2))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            assert f["/g"].shape == (n,)  # whole extent: a view, not a slab

    reset_plan_cache()
    reset_transport_stats()
    w = Wilkins(_mxn_yaml(1, 1, 2), {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    s = transport_stats().snapshot()
    assert s["redist_aligned"] == 1 and s["redist_slabs"] == 0
    # the view's payload bytes still count as shipped; zero bytes were COPIED
    assert s["redist_shipped_bytes"] == s["redist_baseline_bytes"] == n * 8
    assert s["bytes_copied"] == n * 8  # only the create_dataset snapshot


def test_redistribute_through_file_transport(tmp_path):
    """Slab payloads survive the spill container (ownership + attrs)."""
    n = 128
    got = []

    yaml = f"""
tasks:
  - func: producer
    taskCount: 2
    outports:
      - filename: o.h5
        dsets: [{{name: /g, file: 1, memory: 0}}]
  - func: consumer
    taskCount: 2
    nprocs: 1
    inports:
      - filename: o.h5
        redistribute: 1
        dsets: [{{name: /g, file: 1, memory: 0}}]
"""
    lock = threading.Lock()

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(n, dtype=np.float64),
                             ownership=_owned(n, 2))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            d = f["/g"]
            with lock:
                got.append((tuple(d.attrs["redist_box_starts"]),
                            np.asarray(d[:]), dict(d.ownership.blocks)))

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer},
                spill_dir=str(tmp_path))
    w.run(timeout=60)
    assert sorted(s[0] for s, _, _ in got) == [0, 64]
    for (s0,), data, blocks in got:
        np.testing.assert_array_equal(data, np.arange(s0, s0 + 64))
        assert blocks == {0: ((s0,), (64,))}


def test_redist_slab_is_cow_protected():
    """A consumer writing its slab must not corrupt the producer's buffer."""
    f = File("o.h5")
    src = f.create_dataset("/g", data=np.arange(16.0))
    ch = Channel("c", ("p", 0), ("c", 0), "o.h5", ["/g"],
                 redistribute=RedistSpec(axis=0, nslots=2, slot=1, nranks=1))
    out = ch.filter_file(f)
    slab = out["/g"]
    assert slab.shape == (8,)
    assert np.shares_memory(slab.read_direct(), src.read_direct())
    slab[0] = -1.0  # CoW: copies the slab only
    assert slab[0] == -1.0 and src[8] == 8.0
    assert not np.shares_memory(slab.read_direct(), src.read_direct())


def test_pack_all_pads_once_and_matches_per_rank():
    import jax.numpy as jnp

    g = np.arange(37 * 8, dtype=np.float32).reshape(37, 8)  # ragged rows
    plan = CompiledPlan(even_blocks(g.shape, 3), even_blocks(g.shape, 4),
                        g.shape, g.dtype)
    want = plan.execute_global(g)
    got = execute_pack_jax_all(plan, jnp.asarray(g))
    assert len(got) == 4
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(a))


def test_redist_axis_and_subset_writers():
    """redistribute: {axis: 1} decomposes columns; nwriters collapses ranks."""
    n = 32
    got = []

    yaml = f"""
tasks:
  - func: producer
    outports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    nprocs: 4
    nwriters: 2
    inports:
      - filename: o.h5
        redistribute: {{axis: 1}}
        dsets: [{{name: /g, memory: 1}}]
"""

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(4 * n, dtype=np.float64).reshape(4, n))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            got.append(dict(f["/g"].ownership.blocks))

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    # io_procs=2 subset writers along axis 1: two column blocks, not four
    assert got == [{0: ((0, 0), (4, 16)), 1: ((0, 16), (4, 16))}]


# ---------------------------------------------------------------------------
# column-tile pack lowering (axis-1 decompositions on the kernel path)
# ---------------------------------------------------------------------------
def test_pack_mode_detection():
    rowp = CompiledPlan(even_blocks((32, 8), 4), even_blocks((32, 8), 2),
                        (32, 8), np.float32)
    assert rowp.pack_mode == "rows"
    colp = CompiledPlan(even_blocks((32, 8), 4, axis=1),
                        even_blocks((32, 8), 2, axis=1), (32, 8), np.float32)
    assert colp.pack_mode == "cols"
    # cross-axis src: dst runs coalesce across src ranks into full-height
    # column slabs, so the exchange still lowers to the column kernel
    cross = CompiledPlan(even_blocks((32, 8), 4, axis=0),
                         even_blocks((32, 8), 2, axis=1), (32, 8), np.float32)
    assert cross.pack_mode == "cols"
    # a 2-D quadrant tiling is neither full-width nor full-height
    quads = [((0, 0), (8, 8)), ((0, 8), (8, 8)),
             ((8, 0), (8, 8)), ((8, 8), (8, 8))]
    tiled = CompiledPlan([((0, 0), (16, 16))], quads, (16, 16), np.float32)
    assert tiled.pack_mode is None
    oned = CompiledPlan(even_blocks((32,), 4), even_blocks((32,), 2),
                        (32,), np.float32)
    assert oned.pack_mode is None


def test_pack_executor_cols_matches_numpy_scatter(monkeypatch):
    import jax.numpy as jnp

    from repro.kernels import pack

    rng = np.random.default_rng(11)
    default = pack.BLOCK_BYTES
    # 481 columns take four 128-lane tiles and a ragged one; a 4 KiB block
    # budget moves 40 rows in five blocks of 8
    for rows, cols, m_src, m_dst, budget in [
        (8, 64, 4, 2, default), (16, 40, 3, 3, default),
        (8, 481, 2, 5, default), (40, 300, 3, 4, 4096),
    ]:
        monkeypatch.setattr(pack, "BLOCK_BYTES", budget)
        g = rng.normal(size=(rows, cols)).astype(np.float32)
        src = even_blocks(g.shape, m_src, axis=1)
        dst = even_blocks(g.shape, m_dst, axis=1)
        plan = CompiledPlan(src, dst, g.shape, g.dtype)
        assert plan.pack_mode == "cols"
        want = plan.execute_global(g)
        gj = jnp.asarray(g)
        for r in range(m_dst):
            got = np.asarray(execute_pack_jax(plan, r, gj))
            np.testing.assert_array_equal(got, want[r])
        allr = execute_pack_jax_all(plan, jnp.asarray(g))
        for w, a in zip(want, allr):
            np.testing.assert_array_equal(w, np.asarray(a))


def test_pack_executor_rejects_unlowerable_plans():
    import jax.numpy as jnp

    quads = [((0, 0), (8, 8)), ((0, 8), (8, 8)),
             ((8, 0), (8, 8)), ((8, 8), (8, 8))]
    plan = CompiledPlan([((0, 0), (16, 16))], quads, (16, 16), np.float32)
    with pytest.raises(ValueError, match="not pack-kernel lowerable"):
        execute_pack_jax(plan, 0, jnp.zeros((16, 16), jnp.float32))


def test_pack_executor_cross_axis_exchange():
    """src along axis 0, dst along axis 1: runs coalesce to full-height
    column slabs and the exchange stays on the kernel path."""
    import jax.numpy as jnp

    g = np.arange(32 * 12, dtype=np.float32).reshape(32, 12)
    plan = CompiledPlan(even_blocks(g.shape, 4, axis=0),
                        even_blocks(g.shape, 3, axis=1), g.shape, g.dtype)
    want = plan.execute_global(g)
    got = execute_pack_jax_all(plan, jnp.asarray(g))
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(a))


def test_execute_ranks_restriction_matches_full():
    g = np.arange(80.0).reshape(16, 5)
    src = even_blocks(g.shape, 4)
    dst = even_blocks(g.shape, 3)
    plan = CompiledPlan(src, dst, g.shape, g.dtype)
    full = plan.execute_global(g)
    sub = plan.execute_global(g, ranks=[2, 0])
    np.testing.assert_array_equal(sub[0], full[2])
    np.testing.assert_array_equal(sub[1], full[0])
    src_blocks = [g[s[0]:s[0] + sh[0]] for (s, sh) in src]
    sub2 = plan.execute(src_blocks, ranks=[1])
    np.testing.assert_array_equal(sub2[0], full[1])


# ---------------------------------------------------------------------------
# async slab prefetch (payload futures on redistributing channels)
# ---------------------------------------------------------------------------
def test_prefetch_default_and_yaml_knob():
    from repro.core import Wilkins

    w = Wilkins(_mxn_yaml(2, 2, 1), {"producer": lambda: None,
                                     "consumer": lambda: None})
    assert all(c.prefetch for c in w.channels)      # redistribute => on
    w2 = Wilkins(_mxn_yaml(2, 2, 1, extra="prefetch: 0"),
                 {"producer": lambda: None, "consumer": lambda: None})
    assert not any(c.prefetch for c in w2.channels)  # knob overrides
    plain = Channel("p", ("p", 0), ("c", 0), "o.h5", ["/g"])
    assert not plain.prefetch                        # no spec => off


@pytest.mark.slow
def test_prefetch_channel_serves_futures_byte_exact():
    """Payloads prepared on the executor arrive bit-exact, with bytes_moved
    and hit/miss accounting landing by delivery time."""
    n, steps = 256, 4
    got = []
    lock = threading.Lock()

    def producer():
        own = _owned(n, 4)
        for t in range(steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(n, dtype=np.float64) + t,
                                 ownership=own)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            time.sleep(0.01)  # give the executor room to finish the NEXT prep
            with lock:
                got.append(np.asarray(f["/g"][:]))

    from repro.core import Wilkins
    reset_plan_cache()
    reset_transport_stats()
    w = Wilkins(_mxn_yaml(4, 2, 2), {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    s = transport_stats().snapshot()
    assert rep.total_served == 4 * steps
    # every served payload was a future and was resolved at delivery
    assert s["prefetch_hits"] + s["prefetch_misses"] == 4 * steps
    assert s["prefetch_prepared_s"] > 0.0
    assert rep.total_bytes_moved == 4 * steps * (n // 2) * 8
    for data in got:
        assert data.shape == (n // 2,)


def test_prefetch_disabled_records_nothing():
    n = 64

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(n, dtype=np.float64),
                             ownership=_owned(n, 2))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break

    from repro.core import Wilkins
    reset_transport_stats()
    w = Wilkins(_mxn_yaml(2, 2, 1, extra="prefetch: 0"),
                {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    s = transport_stats().snapshot()
    assert s["prefetch_hits"] == s["prefetch_misses"] == 0
    assert s["prefetch_prepared_s"] == 0.0
    assert rep.total_bytes_moved > 0     # sync path still accounts in offer


@pytest.mark.slow
def test_prefetch_through_file_transport(tmp_path):
    """Spill writes also ride the executor; payloads still load correctly."""
    n = 128
    got = []
    lock = threading.Lock()

    yaml = """
tasks:
  - func: producer
    taskCount: 2
    outports:
      - filename: o.h5
        dsets: [{name: /g, file: 1, memory: 0}]
  - func: consumer
    taskCount: 2
    nprocs: 1
    inports:
      - filename: o.h5
        redistribute: 1
        dsets: [{name: /g, file: 1, memory: 0}]
"""

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(n, dtype=np.float64),
                             ownership=_owned(n, 2))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            with lock:
                got.append(np.asarray(f["/g"][:]))

    from repro.core import Wilkins
    reset_transport_stats()
    w = Wilkins(yaml, {"producer": producer, "consumer": consumer},
                spill_dir=str(tmp_path))
    w.run(timeout=60)
    assert len(got) == 2
    total = sorted(float(v[0]) for v in got)
    assert total == [0.0, 64.0]
    s = transport_stats().snapshot()
    assert s["prefetch_hits"] + s["prefetch_misses"] == 2


def test_prefetch_prepare_error_reaches_consumer():
    """An exception inside async payload prep must surface in get(), not
    vanish in the executor."""
    from repro.core.channel import Channel as Ch

    f = File("o.h5")
    f.create_dataset("/g", data=np.arange(8.0))
    ch = Ch("c", ("p", 0), ("c", 0), "o.h5", ["/g"],
            redistribute=RedistSpec(axis=0, nslots=2, slot=1, nranks=1))
    ch.filter_file = lambda _f: (_ for _ in ()).throw(RuntimeError("prep boom"))
    assert ch.offer(f)
    with pytest.raises(RuntimeError, match="prep boom"):
        ch.get(timeout=5)


def test_prefetch_prepare_error_unblocks_producer():
    """A failed async prep must not leave the producer parked forever in the
    rendezvous wait: delivery marks the channel done, offer stops serving."""
    from repro.core.channel import Channel as Ch

    f = File("o.h5")
    f.create_dataset("/g", data=np.arange(8.0))
    ch = Ch("c", ("p", 0), ("c", 0), "o.h5", ["/g"],
            redistribute=RedistSpec(axis=0, nslots=2, slot=0, nranks=1))
    ch.filter_file = lambda _f: (_ for _ in ()).throw(OSError("disk full"))
    assert ch.offer(f)                       # queue slot taken by the future
    with pytest.raises(OSError, match="disk full"):
        ch.get(timeout=5)
    # queue_depth=1 and the slot was consumed: a hung channel would block
    # here forever; the failure containment makes offer a no-op instead
    assert ch.offer(f) is False
    assert ch.get(timeout=5) is None         # done, not hung


SHARDED_PRODUCER = textwrap.dedent("""
    import json, threading
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import Wilkins, h5
    from repro.core.datamodel import reset_transport_stats, transport_stats

    YAML = '''
    tasks:
      - func: producer
        nprocs: 2
        outports:
          - filename: o.h5
            dsets: [{name: /g, memory: 1}]
      - func: consumer
        nprocs: 1
        taskCount: 2
        inports:
          - filename: o.h5
            redistribute: {axis: 0}
            dsets: [{name: /g, memory: 1}]
    '''
    steps = 3
    g = np.arange(64 * 8 * 16, dtype=np.float32).reshape(64, 8, 16)
    shards, got = {}, []
    lock = threading.Lock()

    def producer(comm):
        mesh = comm.mesh()
        for t in range(steps):
            x = jax.device_put(g + t, NamedSharding(mesh, P(mesh.axis_names[0])))
            with lock:  # a device array keeps its fetched host value
                shards[t] = {s.index[0].start or 0: np.asarray(s.data)
                             for s in x.addressable_shards}
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=x).attrs["step"] = t

    def consumer(comm):
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                return
            d = f["/g"]
            t, row = int(d.attrs["step"]), d.attrs["redist_box_starts"][0]
            arr = d[:]
            with lock:
                got.append({
                    "instance": comm.instance, "step": t, "row": row,
                    "exact": bool(np.array_equal(arr, (g + t)[row:row + 32])),
                    "shares": bool(np.shares_memory(arr, shards[t][row]))})

    reset_transport_stats()
    Wilkins(YAML, {"producer": producer, "consumer": consumer},
            devices=jax.devices()[:4]).run(timeout=120)
    print("RESULT " + json.dumps({"got": got,
                                  "stats": transport_stats().snapshot()}))
""")


def test_sharded_producer_ships_each_instance_its_own_shard():
    """On four CPU devices, a producer whose field is sharded on axis 0 over
    its two devices feeds a ``redistribute: {axis: 0}`` port of two
    instances: each instance's slab is a view of its own shard's fetched
    value, exact, and the run copies no byte on the host."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    p = subprocess.run([sys.executable, "-c", SHARDED_PRODUCER], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [s for s in p.stdout.splitlines() if s.startswith("RESULT ")]
    assert line, p.stdout[-3000:] + p.stderr[-3000:]
    res = json.loads(line[-1][len("RESULT "):])
    got = sorted((d["instance"], d["step"]) for d in res["got"])
    assert got == [(i, t) for i in range(2) for t in range(3)]
    for d in res["got"]:
        assert d["row"] == 32 * d["instance"] and d["exact"] and d["shares"], d
    s = res["stats"]
    assert s["bytes_copied"] == 0 and s["snapshots_assembled"] == 0, s
    assert s["snapshots_sharded"] == 3 and s["slabs_from_shard"] == 6, s
    assert s["bytes_d2h"] == 3 * 64 * 8 * 16 * 4, s
