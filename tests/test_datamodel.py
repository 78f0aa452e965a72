"""HDF5-style data model: tree ops, hyperslabs, container I/O, glob match."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest
from hypcompat import given, settings, st

from repro.core.datamodel import (BlockOwnership, Dataset, File, Group,
                                  match_file, match_path)


def test_tree_and_paths():
    f = File("a.h5")
    ds = f.create_dataset("/g1/g2/data", data=np.ones((4, 5)))
    assert ds.path == "/g1/g2/data"
    assert f["/g1/g2/data"] is ds
    assert "/g1/g2" in f and "/g1/zzz" not in f
    assert isinstance(f["/g1"], Group)
    with pytest.raises(KeyError):
        f["/nope"]


def test_hyperslab_read_write():
    f = File("a.h5")
    ds = f.create_dataset("/d", shape=(8, 8), dtype=np.float32)
    block = np.arange(6, dtype=np.float32).reshape(2, 3)
    ds.write_slab((2, 4), block)
    np.testing.assert_array_equal(ds.select((2, 4), (2, 3)), block)
    assert ds.nbytes == 8 * 8 * 4


def test_container_roundtrip(tmp_path):
    f = File("snap.h5")
    d1 = f.create_dataset("/grid", data=np.arange(100, dtype=np.uint64))
    d1.attrs["timestep"] = 3
    own = BlockOwnership()
    own.add(0, (0,), (50,))
    own.add(1, (50,), (50,))
    d1.ownership = own
    f.create_dataset("/p/pos", data=np.ones((10, 3), np.float32))

    path = f.save(str(tmp_path))
    g = File.load(path)
    np.testing.assert_array_equal(g["/grid"][:], np.arange(100, dtype=np.uint64))
    assert g["/grid"].attrs["timestep"] == 3
    assert g["/grid"].ownership.blocks[1] == ((50,), (50,))
    assert g.total_bytes() == f.total_bytes()


def test_copy_meta_only():
    f = File("x.h5")
    f.create_dataset("/a/b", data=np.ones((4,)))
    m = f.copy_meta_only()
    assert m["/a/b"].shape == (4,)
    # structural copy: data buffers are fresh
    assert not np.shares_memory(m["/a/b"].read_direct(), f["/a/b"].read_direct())


@pytest.mark.parametrize("pattern,path,want", [
    ("/group1/grid", "/group1/grid", True),
    ("/group1/*", "/group1/grid", True),
    ("/particles/*", "/particles/pos/value", True),   # prefix semantics
    ("/group1/grid", "/group1/particles", False),
    ("/group1", "/group1/grid", True),                # group names subtree
    ("*", "/anything", True),
])
def test_match_path(pattern, path, want):
    assert match_path(pattern, path) is want


@pytest.mark.parametrize("pattern,name,want", [
    ("outfile.h5", "outfile.h5", True),
    ("*.h5", "outfile.h5", True),
    ("plt*.h5", "plt00010.h5", True),
    ("plt*.h5", "out.h5", False),
])
def test_match_file(pattern, name, want):
    assert match_file(pattern, name) is want


@settings(max_examples=50, deadline=None)
@given(st.lists(st.sampled_from(["a", "b", "c", "dd"]), min_size=1, max_size=4))
def test_match_path_reflexive(parts):
    """Any concrete path matches itself (property)."""
    p = "/" + "/".join(parts)
    assert match_path(p, p)


# ---------------------------------------------------------------------------
# CoW share-count thread safety
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_share_race_view_vs_cow_write():
    """Racing ``view()`` against a CoW write must never tear the
    (share, buffer) pair: a view taken mid-materialization could otherwise
    alias the writer's fresh private buffer while holding a stale (or
    fresh-but-unincremented) ``_Share``, so writes leak across the view
    boundary.  Fails before the atomic-capture fix in Dataset.view."""
    import sys
    import threading

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for trial in range(60):
            f = File("race.h5")
            src = f.create_dataset("/g", data=np.zeros(32))
            views = []
            gate = threading.Barrier(3)

            def viewer():
                gate.wait()
                for _ in range(150):
                    views.append(src.view())

            def writer():
                gate.wait()
                for i in range(150):
                    src[0] = float(i + 1)  # CoW materialize + share swap

            ts = [threading.Thread(target=viewer), threading.Thread(target=writer)]
            for t in ts:
                t.start()
            gate.wait()
            for t in ts:
                t.join()
            # CoW invariant: a write through any view must never reach src.
            snap = np.array(src.read_direct())
            for v in views:
                v[0] = -1.0
            np.testing.assert_array_equal(np.asarray(src.read_direct()), snap)
            # and every materialized view is now truly private
            for v in views:
                assert not np.shares_memory(v.read_direct(), src.read_direct())
    finally:
        sys.setswitchinterval(old)


# ---------------------------------------------------------------------------
# Snapshot of a device array: the fetched host value is adopted.  On the CPU
# backend ``np.asarray`` of a jax.Array aliases the device buffer itself, so
# these are the hard case for the "nothing else can write it" argument.
# ---------------------------------------------------------------------------
def _stats_delta(s0):
    from repro.core.datamodel import transport_stats
    s1 = transport_stats().snapshot()
    return {k: s1[k] - s0[k] for k in
            ("bytes_copied", "bytes_d2h", "snapshots_adopted",
             "snapshots_assembled", "cow_copies")}


@pytest.mark.parametrize("major_to_minor", [(0, 1), (1, 0)],
                         ids=["row_major", "column_major"])
def test_device_snapshot_adopts_the_fetched_value(major_to_minor):
    """A TPU lays out narrow-minor arrays such as (N, 2) column-major, and
    their fetched value is Fortran-ordered: adopted all the same."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.layout import Format, Layout

    from repro.core.datamodel import transport_stats

    want = np.arange(16, dtype=np.uint32).reshape(8, 2)
    x = jax.device_put(jnp.asarray(want), Format(
        Layout(major_to_minor=major_to_minor),
        jax.sharding.SingleDeviceSharding(jax.devices()[0])))
    s0 = transport_stats().snapshot()
    ds = File("a.h5").create_dataset("/d", data=x)
    assert _stats_delta(s0) == {"bytes_copied": 0, "bytes_d2h": x.nbytes,
                                "snapshots_adopted": 1,
                                "snapshots_assembled": 0, "cow_copies": 0}
    got = ds.read_direct()
    assert isinstance(got, np.ndarray) and not got.flags.writeable
    assert got.flags.f_contiguous == (major_to_minor == (1, 0))
    with pytest.raises(ValueError):
        got[0] = 1
    np.testing.assert_array_equal(ds[:], want)
    np.testing.assert_array_equal(
        ds.slab_view((2, 0), (3, 2)).read_direct(), want[2:5])


@pytest.mark.parametrize("end", ["delete", "donate"])
def test_write_to_adopted_snapshot_copies_and_outlives_the_source(end):
    import jax
    import jax.numpy as jnp

    from repro.core.datamodel import transport_stats

    x = jnp.arange(16, dtype=jnp.float32)
    f = File("a.h5")
    ds = f.create_dataset("/d", data=x)
    kept = f.create_dataset("/kept", data=x)  # adopted, never written
    s0 = transport_stats().snapshot()
    ds[0] = 99.0
    assert _stats_delta(s0) == {"bytes_copied": x.nbytes, "bytes_d2h": 0,
                                "snapshots_adopted": 0,
                                "snapshots_assembled": 0, "cow_copies": 1}
    want = np.arange(16, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(x), want)  # source unchanged
    written = want.copy()
    written[0] = 99.0
    if end == "delete":
        x.delete()
    else:
        y = jax.jit(lambda a: a * 2.0 + 1.0, donate_argnums=0)(x)
        np.testing.assert_array_equal(np.asarray(y), want * 2.0 + 1.0)
    np.testing.assert_array_equal(ds[:], written)
    np.testing.assert_array_equal(kept[:], want)


@pytest.mark.parametrize("case", ["dtype", "host"])
def test_snapshot_falls_back_to_a_copy(case):
    import jax.numpy as jnp

    from repro.core.datamodel import transport_stats

    want = np.arange(16, dtype=np.float32)
    s0 = transport_stats().snapshot()
    if case == "dtype":
        x = jnp.asarray(want)
        ds = File("a.h5").create_dataset("/d", dtype=np.float64, data=x)
        d2h = x.nbytes
    else:
        x = want.copy()
        ds = File("a.h5").create_dataset("/d", data=x)
        x[:] = -1.0  # the caller reuses its buffer after the write
        d2h = 0
    assert _stats_delta(s0) == {"bytes_copied": ds.nbytes, "bytes_d2h": d2h,
                                "snapshots_adopted": 0,
                                "snapshots_assembled": 0, "cow_copies": 0}
    assert ds[:].dtype == ds.dtype
    np.testing.assert_array_equal(ds[:], want)


# ---------------------------------------------------------------------------
# the snapshot of a device array sharded over several devices: assembled
# from its distinct shards into the Dataset's own buffer, in the open
# ---------------------------------------------------------------------------
SHARDED_SNAPSHOTS = textwrap.dedent("""
    import json, traceback
    import jax, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.datamodel import Dataset, File, transport_stats
    from repro.obs import SpanRecorder

    KEYS = ("bytes_copied", "bytes_d2h", "snapshots_adopted",
            "snapshots_assembled", "cow_copies")
    g = np.arange(64 * 16 * 16, dtype=np.float32).reshape(64, 16, 16)
    devs = jax.devices()
    line = Mesh(np.array(devs[:2]), ("x",))
    square = Mesh(np.array(devs[:4]).reshape(2, 2), ("x", "y"))

    def snap(x, trace=None):
        s0 = transport_stats().snapshot()
        ds = Dataset("d", x.shape, x.dtype, data=x, trace=trace)
        s1 = transport_stats().snapshot()
        np.testing.assert_array_equal(ds[:], g)
        return ds, {k: s1[k] - s0[k] for k in KEYS}

    def sharded_axis0():
        x = jax.device_put(g, NamedSharding(line, P("x")))
        _, d = snap(x)
        assert d == {"bytes_copied": g.nbytes, "bytes_d2h": g.nbytes,
                     "snapshots_adopted": 0, "snapshots_assembled": 1,
                     "cow_copies": 0}, d

    def sharded_traced():
        x = jax.device_put(g, NamedSharding(line, P("x")))
        tr = SpanRecorder()
        snap(x, trace=(tr, "nyx", 0, 3))
        spans = [s for s in tr.spans() if s["ph"] == "X"]
        top = [s for s in spans if s["name"] == "datamodel.snapshot"]
        assert len(top) == 1 and top[0]["parent"] is None, top
        for name in ("datamodel.d2h", "datamodel.assemble"):
            got = [s for s in spans if s["name"] == name]
            assert len(got) == 2, (name, got)
            for s in got:
                assert s["cat"] == "datamodel" and s["step"] == 3, s
                assert s["parent"] == top[0]["id"], s
                assert s["args"]["bytes"] == g.nbytes // 2, s
            assert {s["args"]["device_id"] for s in got} == {
                devs[0].id, devs[1].id}, got
        assert len(spans) == 5, spans

    def replicated():
        x = jax.device_put(g, NamedSharding(line, P()))
        _, d = snap(x)
        assert d == {"bytes_copied": 0, "bytes_d2h": g.nbytes,
                     "snapshots_adopted": 1, "snapshots_assembled": 0,
                     "cow_copies": 0}, d

    def mesh_2x2():
        x = jax.device_put(g, NamedSharding(square, P("x", None)))
        assert len(x.addressable_shards) == 4
        _, d = snap(x)
        assert d == {"bytes_copied": g.nbytes, "bytes_d2h": g.nbytes,
                     "snapshots_adopted": 0, "snapshots_assembled": 1,
                     "cow_copies": 0}, d

    def write_after():
        x = jax.device_put(g, NamedSharding(line, P("x")))
        ds, _ = snap(x)
        ds[0] = -1.0
        want = g.copy()
        want[0] = -1.0
        np.testing.assert_array_equal(ds[:], want)
        np.testing.assert_array_equal(np.asarray(x), g)
        kept = File("a.h5").create_dataset("/kept", data=x)
        np.testing.assert_array_equal(kept[:], g)

    out = {}
    for case in (sharded_axis0, sharded_traced, replicated, mesh_2x2,
                 write_after):
        try:
            case()
            out[case.__name__] = "ok"
        except Exception:
            out[case.__name__] = traceback.format_exc()
    print("RESULTS " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_snapshots():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SHARDED_SNAPSHOTS], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [s for s in out.stdout.splitlines() if s.startswith("RESULTS ")]
    assert line, out.stdout[-3000:] + out.stderr[-3000:]
    return json.loads(line[-1][len("RESULTS "):])


@pytest.mark.parametrize("case", ["sharded_axis0", "sharded_traced",
                                  "replicated", "mesh_2x2", "write_after"])
def test_sharded_device_snapshot(sharded_snapshots, case):
    """On four CPU devices: a field sharded on axis 0 is assembled from its
    shards (one host copy, counted, with a d2h and an assemble span per
    shard under the snapshot span); a fully replicated array is adopted as
    before; a (2, 2) mesh fetches each distinct block once; a write through
    the Dataset leaves the device array as it was."""
    assert sharded_snapshots[case] == "ok", sharded_snapshots[case]
