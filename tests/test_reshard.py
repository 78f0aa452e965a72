"""TaskComm.reshard -- the one-call user face of the M->N subsystem."""

import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro.core import Wilkins, h5
from repro.core.comm import TaskComm, world
from repro.core.datamodel import BlockOwnership, File
from repro.core.redistribute import (RedistSpec, even_blocks, plan_cache,
                                     redistribute_numpy, reset_plan_cache)
from test_redistribute import ragged_blocks


def _spec(axis=0, nslots=1, slot=0, nranks=2):
    return RedistSpec(axis=axis, nslots=nslots, slot=slot, nranks=nranks)


def test_reshard_matches_redistribute_numpy_1d():
    g = np.arange(97.0)
    spec = _spec(nranks=3)
    got = TaskComm().reshard(g, spec, ranks="all")
    want = redistribute_numpy(g, [((0,), g.shape)], spec.dst_boxes(g.shape)[0])
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, a)


def test_reshard_matches_redistribute_numpy_2d_both_axes():
    g = np.arange(23 * 17, dtype=np.float32).reshape(23, 17)
    for axis in (0, 1):
        spec = _spec(axis=axis, nslots=2, slot=1, nranks=2)
        dst, _ = spec.dst_boxes(g.shape)
        want = redistribute_numpy(g, [((0, 0), g.shape)], dst)
        got = TaskComm().reshard(g, spec, ranks="all")
        for w, a in zip(want, got):
            np.testing.assert_array_equal(w, a)
        mine = TaskComm().reshard(g, spec)  # ranks="mine" default
        for r, a in zip(spec.my_ranks(), mine):
            np.testing.assert_array_equal(want[r], a)


def test_reshard_ragged_src_decomposition():
    rng = np.random.default_rng(5)
    g = rng.normal(size=(41, 6))
    src = ragged_blocks(41, 4, rng, shape=g.shape)
    spec = _spec(nslots=3, slot=2, nranks=2)
    dst, _ = spec.dst_boxes(g.shape)
    want = redistribute_numpy(g, src, dst)
    got = TaskComm().reshard(g, spec, src=src, ranks="all")
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, a)


def test_reshard_dataset_ownership_is_src_decomposition():
    f = File("o.h5")
    g = np.arange(64.0)
    own = BlockOwnership()
    for r, (s, sh) in enumerate(even_blocks(g.shape, 4)):
        own.add(r, s, sh)
    ds = f.create_dataset("/g", data=g)
    ds.ownership = own
    spec = _spec(nranks=2)
    reset_plan_cache()
    got = TaskComm().reshard(ds, spec, ranks="all")
    want = redistribute_numpy(g, [own.blocks[r] for r in range(4)],
                              spec.dst_boxes(g.shape)[0])
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, a)
    # the plan key is the dataset's REAL ownership, not one global block
    assert plan_cache().snapshot()["misses"] == 1


def test_reshard_4to2_axis1_device_pack_path():
    """Acceptance: 4->2 axis-1 decomposition, bit-exact through the pack
    kernel (prefer="pack" forbids any numpy fallback)."""
    import jax
    import jax.numpy as jnp

    g = np.arange(16 * 52, dtype=np.float32).reshape(16, 52)
    src = even_blocks(g.shape, 4, axis=1)
    spec = RedistSpec(axis=1, nslots=2, slot=0, nranks=1)
    dst, _ = spec.dst_boxes(g.shape)
    want = redistribute_numpy(g, src, dst)
    got = TaskComm().reshard(jnp.asarray(g), spec, src=src, ranks="all",
                             prefer="pack")
    assert all(isinstance(b, jax.Array) for b in got)
    for w, a in zip(want, got):
        np.testing.assert_array_equal(w, np.asarray(a))
    plan = plan_cache().get(src, dst, g.shape, g.dtype)
    assert plan.pack_mode == "cols"


SHARDED = textwrap.dedent("""
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.comm import TaskComm
    from repro.core.datamodel import reset_transport_stats, transport_stats
    from repro.core.redistribute import RedistSpec, redistribute_numpy

    comm = TaskComm(devices=jax.devices())
    g = np.arange(32 * 256, dtype=np.float32).reshape(32, 256)
    x = jax.device_put(g, NamedSharding(comm.mesh(), P("data")))
    assert len(x.sharding.device_set) == 2
    for axis in (0, 1):
        spec = RedistSpec(axis=axis, nslots=1, slot=0, nranks=3)
        reset_transport_stats()
        got = comm.reshard(x, spec, ranks="all")      # prefer="auto"
        s = transport_stats().snapshot()
        assert (s["reshard_pack"], s["reshard_numpy"]) == (1, 0), s
        dst, _ = spec.dst_boxes(g.shape)
        want = redistribute_numpy(g, [((0, 0), g.shape)], dst)
        for w, a in zip(want, got):
            assert isinstance(a, jax.Array)
            assert a.devices() <= x.sharding.device_set
            np.testing.assert_array_equal(w, np.asarray(a))
    print("SHARDED_OK")
""")


def test_reshard_sharded_device_array_takes_pack_path():
    """A field sharded over two devices reshards on the kernels under
    prefer="auto" (gathered onto one of its own devices), never by a
    silent host round trip through the numpy scatter."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SHARDED_OK" in out.stdout


def test_reshard_device_rows_pack_path():
    import jax.numpy as jnp

    g = np.arange(37 * 8, dtype=np.float32).reshape(37, 8)
    spec = _spec(nslots=2, slot=1, nranks=2)
    dst, _ = spec.dst_boxes(g.shape)
    want = redistribute_numpy(g, [((0, 0), g.shape)], dst)
    got = TaskComm().reshard(jnp.asarray(g), spec, prefer="pack")
    for r, a in zip(spec.my_ranks(), got):
        np.testing.assert_array_equal(want[r], np.asarray(a))


def test_reshard_prefer_pack_raises_when_unlowerable():
    spec = _spec(nranks=2)
    with pytest.raises(ValueError, match="pack-kernel path unavailable"):
        TaskComm().reshard(np.zeros(8), spec, prefer="pack")  # numpy + 1-D


def test_reshard_spec_resolution_errors():
    c = TaskComm()
    with pytest.raises(ValueError, match="no RedistSpec wired"):
        c.reshard(np.zeros(8))
    c2 = TaskComm(redist_specs={"a.h5": _spec(nranks=1),
                                "b.h5": _spec(nranks=2)})
    with pytest.raises(ValueError, match="distinct RedistSpecs"):
        c2.reshard(np.zeros(8))
    with pytest.raises(ValueError, match="no RedistSpec for port"):
        c2.reshard(np.zeros(8), port="c.h5")
    # port= selects; sole-spec comms resolve implicitly
    assert len(c2.reshard(np.zeros(8), port="b.h5", ranks="all")) == 2
    c3 = TaskComm(redist_specs={"a.h5": _spec(nranks=4)})
    assert len(c3.reshard(np.zeros(8), ranks="all")) == 4


def test_reshard_rank_out_of_range():
    with pytest.raises(ValueError, match="out of range"):
        TaskComm().reshard(np.zeros(8), _spec(nranks=2), ranks=[5])


def test_reshard_in_workflow_consumer_slab():
    """End-to-end: consumers receive their slab over a redistributing
    channel and reshard it onto their logical ranks with one call."""
    yaml = """
tasks:
  - func: producer
    taskCount: 4
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    taskCount: 2
    nprocs: 2
    inports:
      - filename: o.h5
        redistribute: 1
        dsets: [{name: /g, memory: 1}]
"""
    n = 64
    g = np.arange(n, dtype=np.float64)
    own = BlockOwnership()
    for r, (s, sh) in enumerate(even_blocks(g.shape, 4)):
        own.add(r, s, sh)
    got = {}
    lock = threading.Lock()

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=g, ownership=own)

    def consumer(comm):
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            blocks = comm.reshard(f["/g"])  # spec resolved from the driver
            with lock:
                got[comm.instance] = [np.asarray(b) for b in blocks]

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    assert sorted(got) == [0, 1]
    for inst in (0, 1):
        spec = RedistSpec(axis=0, nslots=2, slot=inst, nranks=2)
        dst, _ = spec.dst_boxes(g.shape)
        assert len(got[inst]) == 2
        for r, b in zip(spec.my_ranks(), got[inst]):
            starts, shape = dst[r]
            np.testing.assert_array_equal(
                b, g[starts[0]:starts[0] + shape[0]])


def test_reshard_slab_rejects_foreign_ranks():
    """A received slab can only be resharded onto the ranks it covers."""
    f = File("o.h5")
    ds = f.create_dataset("/g", data=np.arange(32.0))
    ds.attrs["redist_global_shape"] = [64]
    ds.attrs["redist_box_starts"] = [32]
    spec = RedistSpec(axis=0, nslots=2, slot=1, nranks=2)
    # my ranks (2, 3) live inside the slab: fine
    blocks = TaskComm().reshard(ds, spec)
    np.testing.assert_array_equal(blocks[0], np.arange(0.0, 16.0))
    np.testing.assert_array_equal(blocks[1], np.arange(16.0, 32.0))
    # rank 0 belongs to the sibling instance's slab
    with pytest.raises(ValueError, match="not covered by the received slab"):
        TaskComm().reshard(ds, spec, ranks=[0])


# ---------------------------------------------------------------------------
# YAML producer ownership (outports: {ownership: {axis: A}})
# ---------------------------------------------------------------------------
def _graph(yaml):
    from repro.core import WorkflowGraph
    return WorkflowGraph.from_yaml(yaml)


def test_yaml_ownership_parses():
    g = _graph("""
tasks:
  - func: p
    nprocs: 4
    outports:
      - filename: o.h5
        ownership: {axis: 1}
        dsets: [{name: /g, memory: 1}]
""")
    port = g.tasks["p"].outports[0]
    assert port.ownership and port.own_axis == 1 and port.own_nranks is None
    g2 = _graph("""
tasks:
  - func: p
    nprocs: 4
    outports:
      - filename: o.h5
        ownership: {nranks: 4}
""")
    assert g2.tasks["p"].outports[0].own_nranks == 4


@pytest.mark.parametrize("ownership, err", [
    ("{axis: -1}", "axis must be >= 0"),
    ("{nranks: 0}", "nranks must be >= 1"),
    ("{axis: 0, blocks: 3}", "unknown ownership keys"),
])
def test_yaml_ownership_bad_values(ownership, err):
    with pytest.raises(ValueError, match=err):
        _graph(f"""
tasks:
  - func: p
    outports:
      - filename: o.h5
        ownership: {ownership}
""")


def test_yaml_ownership_mismatched_nranks():
    with pytest.raises(ValueError, match="matches neither nprocs=4 nor nwriters=4"):
        _graph("""
tasks:
  - func: p
    nprocs: 4
    outports:
      - filename: o.h5
        ownership: {nranks: 3}
""")
    # nwriters is an accepted block count (subset writers)
    g = _graph("""
tasks:
  - func: p
    nprocs: 4
    nwriters: 2
    outports:
      - filename: o.h5
        ownership: {nranks: 2}
""")
    assert g.tasks["p"].outports[0].own_nranks == 2


def test_yaml_ownership_rejected_on_inports():
    with pytest.raises(ValueError, match="ownership is an outport declaration"):
        _graph("""
tasks:
  - func: c
    inports:
      - filename: o.h5
        ownership: 1
""")


def test_vol_stamps_ownership_at_close():
    from repro.core.vol import VOL

    vol = VOL("p", nprocs=4)
    vol.set_ownership("o.h5", axis=0, nranks=4)
    f = File("o.h5")
    f.create_dataset("/g", data=np.arange(16.0))
    pre = BlockOwnership()
    pre.add(0, (0,), (16,))
    f.create_dataset("/h", data=np.arange(16.0)).ownership = pre
    f.create_dataset("/s", data=np.float64(3.0), shape=(), dtype=np.float64)
    vol.on_file_close(f)
    assert f["/g"].ownership.blocks == {
        0: ((0,), (4,)), 1: ((4,), (4,)), 2: ((8,), (4,)), 3: ((12,), (4,))}
    assert f["/h"].ownership is pre          # explicit ownership wins
    assert f["/s"].ownership is None         # scalars skipped


def test_vol_ownership_axis_out_of_range_is_clear():
    from repro.core.vol import VOL

    vol = VOL("p", nprocs=2)
    vol.set_ownership("o.h5", axis=2, nranks=2)
    f = File("o.h5")
    f.create_dataset("/g", data=np.arange(8.0))
    with pytest.raises(ValueError, match="axis 2 out of range"):
        vol.on_file_close(f)


def test_yaml_ownership_flows_into_plan_src():
    """Producer declares ownership in YAML only; the redistribution plan
    sees the 4-block src decomposition, not one global block."""
    yaml = """
tasks:
  - func: producer
    nprocs: 4
    outports:
      - filename: o.h5
        ownership: 1
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    taskCount: 2
    nprocs: 1
    inports:
      - filename: o.h5
        redistribute: 1
        dsets: [{name: /g, memory: 1}]
"""
    n = 64
    got = {}
    lock = threading.Lock()

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(n, dtype=np.float64))

    def consumer(comm):
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            with lock:
                got[comm.instance] = np.asarray(f["/g"][:])

    reset_plan_cache()
    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    np.testing.assert_array_equal(got[0], np.arange(32.0))
    np.testing.assert_array_equal(got[1], np.arange(32.0, 64.0))
    src4 = even_blocks((n,), 4)
    dst, _ = RedistSpec(axis=0, nslots=2, slot=0, nranks=1).dst_boxes((n,))
    plan = plan_cache().get(src4, dst, (n,), np.float64)
    assert len(plan.src) == 4   # already compiled during the run (cache hit)
    assert plan_cache().snapshot()["misses"] == 1


def test_yaml_prefetch_rejected_on_outports():
    with pytest.raises(ValueError, match="prefetch is an inport declaration"):
        _graph("""
tasks:
  - func: p
    outports:
      - filename: o.h5
        prefetch: 1
""")


def test_reshard_producer_wired_spec_requires_explicit_ranks():
    """A producer feeding a redistributing port has no 'mine': the default
    reshard errors clearly; ranks='all' sees the full consumer layout."""
    yaml = """
tasks:
  - func: producer
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    taskCount: 2
    nprocs: 2
    inports:
      - filename: o.h5
        redistribute: 1
        dsets: [{name: /g, memory: 1}]
"""
    n = 32
    g = np.arange(n, dtype=np.float64)
    results = {}

    def producer(comm):
        with pytest.raises(ValueError, match="has no 'mine'"):
            comm.reshard(g)
        results["all"] = comm.reshard(g, ranks="all")
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=g)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    assert len(results["all"]) == 4          # 2 slots x 2 ranks
    np.testing.assert_array_equal(results["all"][0], g[:8])
    np.testing.assert_array_equal(results["all"][3], g[24:])
