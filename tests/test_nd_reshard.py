"""N-D pack lowering, device-slab dispatch, per-edge prefetch depth, and the
PR-4 bugfix regressions (io_freq validation, prefetch executor lifecycle,
restricted-world mesh errors)."""

import threading
import time

import numpy as np
import pytest

from repro.core import Wilkins, h5
from repro.core.channel import (Channel, DEFAULT_PREFETCH_DEPTH, FlowControl,
                                PrefetchPool, configure_prefetch_pool,
                                shutdown_prefetch_pool)
from repro.core import channel as channel_mod
from repro.core.comm import TaskComm
from repro.core.datamodel import (BlockOwnership, File, is_device_array,
                                  reset_transport_stats, transport_stats)
from repro.core.graph import WorkflowGraph
from repro.core.redistribute import (CompiledPlan, RedistSpec, even_blocks,
                                     execute_pack_jax, execute_pack_jax_all,
                                     plan_cache, redistribute_numpy,
                                     reset_plan_cache)


# ---------------------------------------------------------------------------
# N-D pack lowering (flatten transform)
# ---------------------------------------------------------------------------
def _ref(g, src, dst):
    return redistribute_numpy(g, src, dst)


# block_bytes: None keeps kernels.pack.BLOCK_BYTES; a smaller budget makes
# pack_tiling's own choice cut the frame into several blocks or tiles
@pytest.mark.parametrize("shape, axis, m_src, m_dst, block_bytes", [
    ((37, 5, 6), 0, 4, 3, None),    # 3-D rows lowering (ragged axis extent)
    ((6, 40, 3), 1, 3, 2, None),  # 3-D middle axis -> flattened cols, scale>1
    ((4, 6, 23), 2, 4, 5, None),  # 3-D last axis
    ((3, 4, 5, 23), 3, 4, 5, 736),  # 4-D last axis, ragged 8-row blocks
    ((23, 3, 4, 5), 0, 5, 2, None),  # 4-D rows
    ((3, 17, 4, 5), 1, 2, 3, 1536),  # 4-D middle axis, tiles off scale runs
], ids=["shape0-0-4-3-4", "shape1-1-3-2-4", "shape2-2-4-5-4",
        "shape3-3-4-5-4", "shape4-0-5-2-8", "shape5-1-2-3-4"])
def test_nd_pack_matches_numpy_reference(shape, axis, m_src, m_dst,
                                         block_bytes, monkeypatch):
    import jax.numpy as jnp

    from repro.kernels import pack

    if block_bytes is not None:
        monkeypatch.setattr(pack, "BLOCK_BYTES", block_bytes)
    rng = np.random.default_rng(hash(shape) % 2**31)
    g = rng.normal(size=shape).astype(np.float32)
    src = even_blocks(shape, m_src, axis=axis)
    dst = even_blocks(shape, m_dst, axis=axis)
    plan = CompiledPlan(src, dst, shape, g.dtype)
    assert plan.pack_mode == ("rows" if axis == 0 else "cols")
    assert plan.pack_axis == axis
    want = _ref(g, src, dst)
    got = execute_pack_jax_all(plan, jnp.asarray(g))
    assert len(got) == m_dst
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(a))
    # single-rank entry point agrees
    one = execute_pack_jax(plan, m_dst - 1, jnp.asarray(g))
    np.testing.assert_array_equal(want[-1], np.asarray(one))


def test_nd_cross_axis_exchange_lowers_via_dst_axis():
    """src along axis 0, dst along axis 2: per-dst runs coalesce to
    full-extent axis-2 slabs, so the exchange stays on the kernel path."""
    import jax.numpy as jnp

    g = np.arange(8 * 3 * 24, dtype=np.float32).reshape(8, 3, 24)
    plan = CompiledPlan(even_blocks(g.shape, 4, axis=0),
                        even_blocks(g.shape, 3, axis=2), g.shape, g.dtype)
    assert plan.pack_mode == "cols" and plan.pack_axis == 2
    want = plan.execute_global(g)
    got = execute_pack_jax_all(plan, jnp.asarray(g))
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(a))


def test_nd_genuinely_cross_axis_falls_back_to_numpy():
    """A 3-D quadrant tiling decomposes TWO axes: no single-axis flatten
    exists, pack_mode is None, and reshard takes the scatter executors."""
    import jax.numpy as jnp

    shape = (8, 8, 3)
    quads = [((0, 0, 0), (4, 4, 3)), ((0, 4, 0), (4, 4, 3)),
             ((4, 0, 0), (4, 4, 3)), ((4, 4, 0), (4, 4, 3))]
    plan = CompiledPlan([((0, 0, 0), shape)], quads, shape, np.float32)
    assert plan.pack_mode is None and plan.pack_axis is None
    with pytest.raises(ValueError, match="not pack-kernel lowerable"):
        execute_pack_jax(plan, 0, jnp.zeros(shape, jnp.float32))
    g = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    want = redistribute_numpy(g, [((0, 0, 0), shape)], quads)
    got = plan.execute_global(g)
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, a)


def test_reshard_rank3_device_array_takes_pack_path():
    """Acceptance: rank-3 reshard of a device array runs the pack kernels
    (prefer="pack" forbids numpy fallback) and is byte-identical to
    redistribute_numpy."""
    import jax
    import jax.numpy as jnp

    g = np.arange(24 * 5 * 6, dtype=np.float32).reshape(24, 5, 6)
    spec = RedistSpec(axis=0, nslots=2, slot=0, nranks=2)
    dst, _ = spec.dst_boxes(g.shape)
    want = redistribute_numpy(g, [((0, 0, 0), g.shape)], dst)
    reset_plan_cache()
    reset_transport_stats()
    got = TaskComm().reshard(jnp.asarray(g), spec, ranks="all",
                             prefer="pack")
    assert all(isinstance(b, jax.Array) for b in got)
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(a))
    plan = plan_cache().get([((0, 0, 0), g.shape)], dst, g.shape, g.dtype)
    assert plan.pack_mode == "rows"   # no numpy fallback was possible
    s = transport_stats().snapshot()
    assert s["reshard_pack"] == 1 and s["reshard_numpy"] == 0


def test_reshard_rank3_middle_axis_device_array():
    import jax.numpy as jnp

    g = np.arange(6 * 40 * 3, dtype=np.float32).reshape(6, 40, 3)
    spec = RedistSpec(axis=1, nslots=2, slot=1, nranks=2)
    dst, _ = spec.dst_boxes(g.shape)
    want = redistribute_numpy(g, [((0, 0, 0), g.shape)], dst)
    got = TaskComm().reshard(jnp.asarray(g), spec, prefer="pack")
    for r, a in zip(spec.my_ranks(), got):
        np.testing.assert_array_equal(want[r], np.asarray(a))


# ---------------------------------------------------------------------------
# device-slab pack-path dispatch
# ---------------------------------------------------------------------------
def _slab_dataset(g, spec, slot, data_transform=lambda x: x):
    """Build the Dataset a redistributing channel would ship to ``slot``."""
    dst, slots = spec.dst_boxes(g.shape)
    starts, shape = slots[slot]
    slc = tuple(slice(s, s + n) for s, n in zip(starts, shape))
    f = File("o.h5")
    ds = f.create_dataset("/g", data=data_transform(g[slc]), copy=False)
    ds.attrs["redist_global_shape"] = list(g.shape)
    ds.attrs["redist_box_starts"] = list(starts)
    return ds, dst


def test_device_slab_dataset_dispatches_to_pack_kernels():
    """A received slab backed by a device array reshards on the kernel path:
    the dispatch probes the READ BUFFER (a Dataset is not a jax.Array), and
    the gathers run in slab-local source coordinates."""
    import jax
    import jax.numpy as jnp

    g = np.arange(32 * 5 * 2, dtype=np.float32).reshape(32, 5, 2)
    spec = RedistSpec(axis=0, nslots=2, slot=1, nranks=2)
    ds, dst = _slab_dataset(g, spec, 1, jnp.asarray)
    assert is_device_array(ds.read_direct())
    want = redistribute_numpy(g, [((0, 0, 0), g.shape)], dst)
    blocks = TaskComm().reshard(ds, spec, prefer="pack")
    assert all(isinstance(b, jax.Array) for b in blocks)
    for r, b in zip(spec.my_ranks(), blocks):
        np.testing.assert_array_equal(want[r], np.asarray(b))
    # foreign ranks live outside the received slab, kernel path or not
    with pytest.raises(ValueError, match="not covered by the received slab"):
        TaskComm().reshard(ds, spec, ranks=[0], prefer="pack")


def test_device_slab_2d_axis1_pack_dispatch():
    import jax.numpy as jnp

    g = np.arange(8 * 48, dtype=np.float32).reshape(8, 48)
    spec = RedistSpec(axis=1, nslots=2, slot=0, nranks=2)
    ds, dst = _slab_dataset(g, spec, 0, jnp.asarray)
    want = redistribute_numpy(g, [((0, 0), g.shape)], dst)
    blocks = TaskComm().reshard(ds, spec, prefer="pack")
    for r, b in zip(spec.my_ranks(), blocks):
        np.testing.assert_array_equal(want[r], np.asarray(b))


def test_slab_covering_only_run_head_raises_not_corrupts():
    """A slab that covers the START of a dst rank's run but not its tail
    must raise -- a clamped out-of-bounds tile DMA would silently return
    duplicated/zero rows instead."""
    import jax.numpy as jnp

    shape = (100, 8)
    dst = [((40, 0), (30, 8))]       # the rank's run needs rows 40-69
    plan = CompiledPlan([((0, 0), shape)], dst, shape, np.float32)
    slab_box = ((40, 0), (10, 8))    # but the slab holds rows 40-49 only
    slab = jnp.zeros((10, 8), jnp.float32)
    with pytest.raises(ValueError, match="does not cover this rank"):
        execute_pack_jax(plan, 0, slab, slab_box=slab_box)


def test_host_slab_dataset_still_uses_numpy_scatter():
    g = np.arange(32 * 3, dtype=np.float64).reshape(32, 3)
    spec = RedistSpec(axis=0, nslots=2, slot=0, nranks=2)
    ds, dst = _slab_dataset(g, spec, 0, np.array)
    want = redistribute_numpy(g, [((0, 0), g.shape)], dst)
    blocks = TaskComm().reshard(ds, spec)
    assert all(isinstance(b, np.ndarray) for b in blocks)
    for r, b in zip(spec.my_ranks(), blocks):
        np.testing.assert_array_equal(want[r], b)


def test_device_dataset_cow_write_materializes_host_copy():
    """Device buffers are immutable: a write through the Dataset CoW layer
    lands in a private host copy, never corrupting the device payload."""
    import jax.numpy as jnp

    f = File("o.h5")
    src = jnp.arange(8.0)
    ds = f.create_dataset("/g", data=src, copy=False)
    assert is_device_array(ds.read_direct())
    ds[0] = -1.0
    got = ds.read_direct()
    assert isinstance(got, np.ndarray) and got[0] == -1.0
    assert float(src[0]) == 0.0


# ---------------------------------------------------------------------------
# satellite: io_freq validation at graph parse time
# ---------------------------------------------------------------------------
def test_io_freq_typo_rejected_at_parse_naming_task_and_port():
    yaml = """
tasks:
  - func: sim
    outports:
      - filename: o.h5
  - func: ana
    inports:
      - filename: o.h5
        io_freq: -2
"""
    with pytest.raises(ValueError, match=r"task 'ana' port 'o.h5'.*io_freq -2"):
        WorkflowGraph.from_yaml(yaml)


def test_io_freq_valid_values_still_parse():
    for freq in (0, 1, 2, 7, -1):
        g = WorkflowGraph.from_yaml(f"""
tasks:
  - func: ana
    inports:
      - filename: o.h5
        io_freq: {freq}
""")
        assert g.tasks["ana"].inports[0].io_freq == freq


def test_flow_control_decode_still_guards():
    with pytest.raises(ValueError, match="invalid io_freq -2"):
        FlowControl.from_io_freq(-2)


# ---------------------------------------------------------------------------
# satellite: prefetch executor lifecycle
# ---------------------------------------------------------------------------
def _mxn_yaml(extra=""):
    return f"""
tasks:
  - func: producer
    taskCount: 2
    outports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    taskCount: 2
    nprocs: 1
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


def _run_pool_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("wilkins-prefetch-run")]


def _wait_no_run_pool_threads(timeout=5.0):
    deadline = time.monotonic() + timeout
    while _run_pool_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    return not _run_pool_threads()


def test_prefetch_pool_torn_down_after_successful_run():
    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(64.0), ownership=_owned(64, 2))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break

    shutdown_prefetch_pool()
    w = Wilkins(_mxn_yaml(), {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    # the run-scoped pool was shut down (workers drained) and the channels
    # detached; the run never touched the module-default pool
    assert all(c._prefetch_pool is None for c in w.channels)
    assert _wait_no_run_pool_threads()
    assert channel_mod._PREFETCH_POOL is None


def test_prefetch_pool_torn_down_on_error_path():
    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(64.0), ownership=_owned(64, 2))

    def consumer():
        raise RuntimeError("consumer boom")

    shutdown_prefetch_pool()
    w = Wilkins(_mxn_yaml(), {"producer": producer, "consumer": consumer})
    with pytest.raises(RuntimeError, match="consumer boom"):
        w.run(timeout=60)
    assert all(c._prefetch_pool is None for c in w.channels)
    assert _wait_no_run_pool_threads()
    assert channel_mod._PREFETCH_POOL is None


def test_concurrent_runs_use_independent_pools():
    """Two workflows running in one process must not cancel each other's
    preps: each run owns its pool, injected per channel."""
    barrier = threading.Barrier(2, timeout=30)
    pools = {}
    lock = threading.Lock()

    def make_funcs(tag):
        def producer():
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(64.0),
                                 ownership=_owned(64, 2))

        def consumer():
            while True:
                f = h5.File("o.h5", "r")
                if f is None:
                    break

        return {"producer": producer, "consumer": consumer}

    def run_one(tag):
        w = Wilkins(_mxn_yaml(), make_funcs(tag))
        orig_run = w.run

        barrier.wait()
        rep = orig_run(timeout=60)
        with lock:
            pools[tag] = rep
        return rep

    ts = [threading.Thread(target=run_one, args=(i,), daemon=True)
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(60)
        assert not t.is_alive()
    # both runs completed and served every payload despite overlapping
    # (2x2 round-robin pairing = 2 channels, one serve each)
    assert len(pools) == 2
    for rep in pools.values():
        assert rep.total_served == 2
    assert _wait_no_run_pool_threads()


def test_prefetch_pool_workers_are_daemon_and_drain_on_shutdown():
    pool = PrefetchPool(max_workers=2, thread_name_prefix="t-pool")
    assert all(t.daemon for t in pool._threads)
    assert pool.submit(lambda: 41 + 1).result(timeout=5) == 42
    pool.shutdown()
    deadline = time.monotonic() + 5
    while pool.alive_workers() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert pool.alive_workers() == 0
    with pytest.raises(RuntimeError, match="shut down"):
        pool.submit(lambda: None)


def test_prefetch_pool_shutdown_cancels_queued_preps():
    started = threading.Event()
    release = threading.Event()

    def blocker():
        started.set()
        release.wait(10)
        return "done"

    pool = PrefetchPool(max_workers=1)
    f1 = pool.submit(blocker)
    assert started.wait(5)
    f2 = pool.submit(lambda: "never runs")   # queued behind the blocker
    pool.shutdown()
    assert f2.cancelled()
    release.set()
    assert f1.result(timeout=5) == "done"    # running preps finish normally


def test_configure_prefetch_pool_replaces_and_shuts_old():
    old = configure_prefetch_pool(1)
    new = configure_prefetch_pool(2)
    assert new is not old
    with pytest.raises(RuntimeError):
        old.submit(lambda: None)
    shutdown_prefetch_pool()
    assert channel_mod._PREFETCH_POOL is None


# ---------------------------------------------------------------------------
# per-edge prefetch depth
# ---------------------------------------------------------------------------
def test_prefetch_yaml_depth_parses_and_reaches_channel():
    w = Wilkins(_mxn_yaml(extra="prefetch: 3"),
                {"producer": lambda: None, "consumer": lambda: None})
    assert all(c.prefetch == 3 for c in w.channels)
    w2 = Wilkins(_mxn_yaml(), {"producer": lambda: None,
                               "consumer": lambda: None})
    assert all(c.prefetch == DEFAULT_PREFETCH_DEPTH for c in w2.channels)
    with pytest.raises(ValueError, match="prefetch depth must be >= 0"):
        WorkflowGraph.from_yaml(_mxn_yaml(extra="prefetch: -1"))


@pytest.mark.slow
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_prefetch_depth_bounds_inflight_preps_per_edge(depth):
    """Under contention (slow preps, deep queue) at most ``depth`` payload
    preparations for one edge run concurrently."""
    f = File("o.h5")
    f.create_dataset("/g", data=np.arange(16.0))
    ch = Channel("c", ("p", 0), ("c", 0), "o.h5", ["/g"], queue_depth=8,
                 redistribute=RedistSpec(axis=0, nslots=2, slot=0, nranks=1),
                 prefetch=depth)
    configure_prefetch_pool(8)   # pool never the bottleneck
    lock = threading.Lock()
    state = {"cur": 0, "max": 0}
    orig = ch._prepare

    def slow_prepare(*a, **kw):
        with lock:
            state["cur"] += 1
            state["max"] = max(state["max"], state["cur"])
        try:
            time.sleep(0.05)
            return orig(*a, **kw)
        finally:
            with lock:
                state["cur"] -= 1

    ch._prepare = slow_prepare
    try:
        consumed = []

        def consume():
            while True:
                got = ch.get(timeout=20)
                if got is None:
                    return
                consumed.append(got)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        for _ in range(8):
            assert ch.offer(f)
        ch.finish()
        t.join(30)
        assert not t.is_alive()
        assert len(consumed) == 8
        assert state["max"] <= depth
    finally:
        shutdown_prefetch_pool()


@pytest.mark.slow
def test_prefetch_depth_is_per_edge_not_global():
    """Two edges with depth 1 each may overlap with each other (2 preps in
    flight globally) but never within one edge."""
    f = File("o.h5")
    f.create_dataset("/g", data=np.arange(16.0))
    spec = RedistSpec(axis=0, nslots=2, slot=0, nranks=1)
    chans = [Channel(f"c{i}", ("p", 0), ("c", i), "o.h5", ["/g"],
                     queue_depth=4, redistribute=spec, prefetch=1)
             for i in range(2)]
    configure_prefetch_pool(4)
    lock = threading.Lock()
    per_edge = {c.name: {"cur": 0, "max": 0} for c in chans}
    global_state = {"cur": 0, "max": 0}

    def wrap(ch):
        orig = ch._prepare

        def slow(*a, **kw):
            with lock:
                per_edge[ch.name]["cur"] += 1
                per_edge[ch.name]["max"] = max(per_edge[ch.name]["max"],
                                               per_edge[ch.name]["cur"])
                global_state["cur"] += 1
                global_state["max"] = max(global_state["max"],
                                          global_state["cur"])
            try:
                time.sleep(0.05)
                return orig(*a, **kw)
            finally:
                with lock:
                    per_edge[ch.name]["cur"] -= 1
                    global_state["cur"] -= 1

        ch._prepare = slow

    for c in chans:
        wrap(c)
    try:
        threads = []

        def drain(ch):
            while ch.get(timeout=20) is not None:
                pass

        for c in chans:
            t = threading.Thread(target=drain, args=(c,), daemon=True)
            t.start()
            threads.append(t)

        def produce(ch):
            for _ in range(4):
                ch.offer(f)
            ch.finish()

        producers = [threading.Thread(target=produce, args=(c,), daemon=True)
                     for c in chans]
        for p in producers:
            p.start()
        for th in producers + threads:
            th.join(30)
            assert not th.is_alive()
        for c in chans:
            assert per_edge[c.name]["max"] <= 1
    finally:
        shutdown_prefetch_pool()


# ---------------------------------------------------------------------------
# satellite: restricted-world mesh validation
# ---------------------------------------------------------------------------
def test_mesh_overcommit_raises_clear_error():
    comm = TaskComm(task="sim", devices=[object(), object()])
    with pytest.raises(ValueError, match=r"task 'sim'.*mesh shape \(4,\) "
                                         r"needs 4 devices.*holds only 2"):
        comm.mesh(shape=(4,))
    with pytest.raises(ValueError, match="restricted device group"):
        comm.mesh(shape=(2, 2))


def test_mesh_within_budget_still_builds():
    import jax

    comm = TaskComm(task="sim", devices=list(jax.devices())[:1])
    m = comm.mesh(shape=(1,))
    assert m.devices.shape == (1,)


# ---------------------------------------------------------------------------
# WorkflowReport.summary counters (acceptance)
# ---------------------------------------------------------------------------
def test_summary_prints_prefetch_and_plan_cache_counters():
    n, steps = 128, 3

    def producer():
        own = _owned(n, 2)
        for _ in range(steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(n, dtype=np.float64),
                                 ownership=own)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break

    reset_plan_cache()
    reset_transport_stats()
    w = Wilkins(_mxn_yaml(), {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    s = rep.summary()
    assert "prefetch: hits=" in s and "blocked_s=" in s
    assert "plan_cache: size=" in s and "hit_rate=" in s
    assert "redist: planned=" in s
    assert rep.transport["prefetch_hits"] + rep.transport["prefetch_misses"] > 0
    assert rep.plan_cache["misses"] >= 1


def test_summary_counters_present_on_error_report():
    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(16.0), ownership=_owned(16, 2))

    def consumer():
        raise RuntimeError("boom")

    w = Wilkins(_mxn_yaml(), {"producer": producer, "consumer": consumer})
    with pytest.raises(RuntimeError) as ei:
        w.run(timeout=60)
    rep = ei.value.report
    assert "plan_cache:" in rep.summary()
