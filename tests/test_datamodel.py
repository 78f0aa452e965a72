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
# a deferred snapshot (its file's close will be dropped) keeps the device
# array unfetched; the first read that needs bytes fetches it, once
# ---------------------------------------------------------------------------
DEFERRED = ("bytes_d2h", "snapshots_deferred", "deferred_fetched")


def _deferred_delta(s0):
    from repro.core.datamodel import transport_stats
    s1 = transport_stats().snapshot()
    return {k: s1[k] - s0[k] for k in DEFERRED}


def _read_as(kind, ds, spill_dir):
    """What a reader of ``kind`` gets from ``ds``: the whole, a slab, a
    write's private copy, or the spilled and reloaded file."""
    if kind == "whole":
        return np.array(ds[:])
    if kind == "slab":
        return np.array(ds.slab_view((2, 0), (3, 4))[:])
    if kind == "private_copy":
        ds[0] = -1.0
        return np.array(ds[:])
    return np.array(File.load(ds.parent.save(spill_dir))["/d"][:])


def _read_expected(kind, want):
    if kind == "slab":
        return want[2:5, :4]
    if kind == "private_copy":
        out = want.copy()
        out[0] = -1.0
        return out
    return want


def test_deferred_snapshot_fetches_nothing_at_write():
    import jax.numpy as jnp

    from repro.core.datamodel import transport_stats

    x = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    s0 = transport_stats().snapshot()
    ds = File("a.h5").create_dataset("/d", data=x, defer=True)
    assert _deferred_delta(s0) == {"bytes_d2h": 0, "snapshots_deferred": 1,
                                   "deferred_fetched": 0}
    assert ds.shape == (8, 4) and ds.dtype == np.float32
    s0 = transport_stats().snapshot()
    host = File("a.h5").create_dataset("/d", data=np.asarray(x), defer=True)
    assert _deferred_delta(s0) == {k: 0 for k in DEFERRED}  # copied as ever
    np.testing.assert_array_equal(host[:], np.asarray(x))


@pytest.mark.parametrize("kind", ["whole", "slab", "private_copy", "save"])
def test_deferred_snapshot_first_read_fetches_once(kind, tmp_path):
    """More readers than cores race to a deferred one-device snapshot: one
    fetch (one ``datamodel.d2h`` span with the write's step), exact values
    for every reader."""
    import threading

    import jax.numpy as jnp

    from repro.core.datamodel import transport_stats
    from repro.obs import SpanRecorder

    n = 2 * (os.cpu_count() or 4)
    # 4 MiB: a fetch long enough for the readers to overlap in it
    want = np.arange(1 << 20, dtype=np.float32).reshape(1 << 12, 1 << 8)
    x = jnp.asarray(want)
    tr = SpanRecorder()
    f = File("a.h5")
    f.create_dataset("/d", data=x, defer=True, trace=(tr, "nyx", 0, 7))
    readers = [f.view()["/d"] for _ in range(n)]
    gate = threading.Barrier(n)
    got = [None] * n
    s0 = transport_stats().snapshot()

    def read(i):
        gate.wait()
        got[i] = _read_as(kind, readers[i], str(tmp_path / str(i)))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=read, args=(i,)) for i in range(n)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=30)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert _deferred_delta(s0) == {"bytes_d2h": x.nbytes,
                                   "snapshots_deferred": 0,
                                   "deferred_fetched": 1}
    for g in got:
        np.testing.assert_array_equal(g, _read_expected(kind, want))
    d2h = [s for s in tr.spans()
           if s["ph"] == "X" and s["name"] == "datamodel.d2h"]
    assert len(d2h) == 1 and d2h[0]["step"] == 7, d2h
    s0 = transport_stats().snapshot()
    np.testing.assert_array_equal(f["/d"][:], want)  # the fetch is kept
    assert _deferred_delta(s0) == {k: 0 for k in DEFERRED}


@pytest.mark.parametrize("end", ["delete", "donate"])
def test_deferred_snapshot_of_a_dead_array_raises(end):
    """A deferred snapshot never returns other bytes: an array deleted or
    donated before the fetch is an error that names the cause."""
    import jax
    import jax.numpy as jnp

    x = jnp.arange(16, dtype=jnp.float32) + 1.0
    ds = File("a.h5").create_dataset("/d", data=x, defer=True)
    if end == "delete":
        x.delete()
    else:
        jax.jit(lambda a: a * 2.0, donate_argnums=0)(x)
    assert x.is_deleted()
    with pytest.raises(ValueError, match="deleted or donated"):
        ds[:]
    kept = File("a.h5").create_dataset("/d", data=jnp.ones(4), defer=True)
    np.testing.assert_array_equal(kept[:], np.ones(4, np.float32))


# ---------------------------------------------------------------------------
# the snapshot of a device array on four devices: kept as its fetched
# blocks, one per distinct shard, and assembled only where a read needs the
# whole of a sharded array; a one-device array is the one-block case
# ---------------------------------------------------------------------------
SHARDED_SNAPSHOTS = textwrap.dedent("""
    import json, tempfile, traceback
    import jax, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core.datamodel import Dataset, File, transport_stats
    from repro.obs import SpanRecorder

    KEYS = ("bytes_copied", "bytes_d2h", "snapshots_adopted",
            "snapshots_sharded", "snapshots_assembled", "slabs_from_shard",
            "cow_copies")
    g = np.arange(64 * 16 * 16, dtype=np.float32).reshape(64, 16, 16)
    devs = jax.devices()
    line = Mesh(np.array(devs[:2]), ("x",))
    square = Mesh(np.array(devs[:4]).reshape(2, 2), ("x", "y"))
    WRITE = {"bytes_copied": 0, "bytes_d2h": g.nbytes,
             "snapshots_adopted": 0, "snapshots_sharded": 1,
             "snapshots_assembled": 0, "slabs_from_shard": 0,
             "cow_copies": 0}
    ASSEMBLE = dict({k: 0 for k in KEYS}, bytes_copied=g.nbytes,
                    snapshots_assembled=1)

    def counted(fn):
        s0 = transport_stats().snapshot()
        out = fn()
        s1 = transport_stats().snapshot()
        return out, {k: s1[k] - s0[k] for k in KEYS}

    def sharded(mesh=line, spec=P("x")):
        return jax.device_put(g, NamedSharding(mesh, spec))

    def shard_value(x, row):
        # the fetched host value of the shard holding ``row`` (replica 0):
        # a device array keeps its fetched value, so this is the one the
        # snapshot kept
        s, = [s for s in x.addressable_shards if s.replica_id == 0
              and (s.index[0].start or 0) <= row < s.index[0].stop]
        return np.asarray(s.data)

    def snap(x, trace=None):
        return counted(lambda: Dataset("d", x.shape, x.dtype, data=x,
                                       trace=trace))

    def sharded_axis0():
        ds, d = snap(sharded())
        assert d == WRITE, d
        got, d = counted(lambda: ds[:])
        np.testing.assert_array_equal(got, g)
        assert d == ASSEMBLE, d
        got, d = counted(lambda: ds[:])  # assembled once per snapshot
        np.testing.assert_array_equal(got, g)
        assert d == {k: 0 for k in KEYS}, d

    def sharded_traced():
        tr = SpanRecorder()
        ds, _ = snap(sharded(), trace=(tr, "nyx", 0, 3))
        spans = [s for s in tr.spans() if s["ph"] == "X"]
        top = [s for s in spans if s["name"] == "datamodel.snapshot"]
        assert len(top) == 1 and top[0]["parent"] is None, top
        d2h = [s for s in spans if s["name"] == "datamodel.d2h"]
        assert all(s["parent"] == top[0]["id"] for s in d2h), d2h
        assert len(spans) == 3, spans  # no assembly at write
        np.testing.assert_array_equal(ds[:], g)
        spans = [s for s in tr.spans() if s["ph"] == "X"]
        assembled = [s for s in spans if s["name"] == "datamodel.assemble"]
        for got in (d2h, assembled):
            assert len(got) == 2, got
            for s in got:
                assert s["cat"] == "datamodel" and s["step"] == 3, s
                assert (s["task"], s["instance"]) == ("nyx", 0), s
                assert s["args"]["bytes"] == g.nbytes // 2, s
            assert {s["args"]["device_id"] for s in got} == {
                devs[0].id, devs[1].id}, got
        assert len(spans) == 5, spans

    def replicated():
        ds, d = snap(sharded(spec=P()))
        np.testing.assert_array_equal(ds[:], g)
        assert d == dict({k: 0 for k in KEYS}, bytes_d2h=g.nbytes,
                         snapshots_adopted=1), d

    def mesh_2x2():
        x = sharded(square, P("x", None))
        assert len(x.addressable_shards) == 4
        ds, d = snap(x)
        assert d == WRITE, d
        got, d = counted(lambda: ds[:])
        np.testing.assert_array_equal(got, g)
        assert d == ASSEMBLE, d

    def write_after():
        x = sharded()
        ds, _ = snap(x)
        _, d = counted(lambda: ds.__setitem__(0, -1.0))
        # assembled straight into the writer's private copy
        assert d == dict(ASSEMBLE, cow_copies=1), d
        want = g.copy()
        want[0] = -1.0
        np.testing.assert_array_equal(ds[:], want)
        np.testing.assert_array_equal(np.asarray(x), g)
        kept = File("a.h5").create_dataset("/kept", data=x)
        np.testing.assert_array_equal(kept[:], g)

    def slab_in_shard():
        x = sharded()
        ds, _ = snap(x)
        slab, d = counted(lambda: ds.slab_view((40, 2, 0), (8, 12, 16)))
        assert d == dict({k: 0 for k in KEYS}, slabs_from_shard=1), d
        got = slab[:]
        assert isinstance(slab._data, np.ndarray)
        assert np.shares_memory(got, shard_value(x, 40))
        assert not np.shares_memory(got, shard_value(x, 0))
        np.testing.assert_array_equal(got, g[40:48, 2:14])

    def slab_across_shards():
        ds, _ = snap(sharded())
        slab, d = counted(lambda: ds.slab_view((24, 0, 0), (16, 16, 16)))
        assert d == ASSEMBLE, d
        np.testing.assert_array_equal(slab[:], g[24:40])
        again, d = counted(lambda: ds.slab_view((30, 0, 0), (4, 16, 16)))
        assert d == {k: 0 for k in KEYS}, d
        np.testing.assert_array_equal(again[:], g[30:34])
        assert np.shares_memory(slab[:], again[:])

    def views_assemble_once():
        f = File("a.h5")
        f.create_dataset("/d", data=sharded())
        a, b = f.view()["/d"], f.view()["/d"].view()
        (ra, rb), d = counted(lambda: (a[:], b[:]))
        assert d == ASSEMBLE, d
        assert np.shares_memory(ra, rb)
        np.testing.assert_array_equal(rb, g)

    def mesh_2x2_slab():
        x = sharded(square, P("x", None))
        ds, _ = snap(x)
        slab, d = counted(lambda: ds.slab_view((32, 0, 0), (32, 16, 16)))
        assert d == dict({k: 0 for k in KEYS}, slabs_from_shard=1), d
        assert np.shares_memory(slab[:], shard_value(x, 32))
        np.testing.assert_array_equal(slab[:], g[32:])

    def write_after_slab():
        x = sharded()
        ds, _ = snap(x)
        sibling = ds.slab_view((0, 0, 0), (32, 16, 16))
        mine = ds.slab_view((32, 0, 0), (32, 16, 16))
        _, d = counted(lambda: mine.__setitem__(0, -1.0))
        # the slab copies its own rows only
        assert d == dict({k: 0 for k in KEYS}, bytes_copied=g.nbytes // 2,
                         cow_copies=1), d
        assert float(mine[0, 0, 0]) == -1.0
        np.testing.assert_array_equal(sibling[:], g[:32])
        np.testing.assert_array_equal(ds[:], g)
        np.testing.assert_array_equal(np.asarray(x), g)

    def spill_round_trip():
        f = File("a.h5")
        f.create_dataset("/d", data=sharded())
        path, d = counted(lambda: f.save(tempfile.mkdtemp()))
        assert d == ASSEMBLE, d
        np.testing.assert_array_equal(File.load(path)["/d"][:], g)

    def one_device():
        x = jax.device_put(g, devs[1])
        ds, d = snap(x)
        assert d == dict({k: 0 for k in KEYS}, bytes_d2h=g.nbytes,
                         snapshots_adopted=1), d
        block = np.asarray(x.addressable_shards[0].data)  # the one block
        got, d = counted(lambda: ds[:])
        assert d == {k: 0 for k in KEYS}, d
        assert np.shares_memory(got, block)
        np.testing.assert_array_equal(got, g)
        slab, d = counted(lambda: ds.slab_view((40, 2, 0), (8, 12, 16)))
        assert d == {k: 0 for k in KEYS}, d
        assert np.shares_memory(slab[:], block)
        np.testing.assert_array_equal(slab[:], g[40:48, 2:14])
        tr = SpanRecorder()
        ds, _ = snap(x, trace=(tr, "nyx", 0, 3))
        np.testing.assert_array_equal(ds[:], g)
        spans = [s for s in tr.spans() if s["ph"] == "X"]
        d2h = [s for s in spans if s["name"] == "datamodel.d2h"]
        assert len(d2h) == 1, d2h
        assert d2h[0]["args"]["device_id"] == devs[1].id, d2h
        assert d2h[0]["args"]["bytes"] == g.nbytes, d2h
        assert not [s for s in spans
                    if s["name"] == "datamodel.assemble"], spans

    def one_device_write():
        x = jax.device_put(g, devs[1])
        ds, _ = snap(x)
        _, d = counted(lambda: ds.__setitem__(0, -1.0))
        assert d == dict({k: 0 for k in KEYS}, bytes_copied=g.nbytes,
                         cow_copies=1), d
        want = g.copy()
        want[0] = -1.0
        np.testing.assert_array_equal(ds[:], want)
        np.testing.assert_array_equal(np.asarray(x), g)

    DEFERRED = ("bytes_d2h", "snapshots_deferred", "deferred_fetched")

    def deferred_delta(s0):
        s1 = transport_stats().snapshot()
        return {k: s1[k] - s0[k] for k in DEFERRED}

    def deferred_reads():
        # two concurrent readers of each kind: one fetch of both shards
        import threading
        for kind in ("whole", "slab", "private_copy", "save"):
            tr = SpanRecorder()
            f = File("a.h5")
            s0 = transport_stats().snapshot()
            f.create_dataset("/d", data=sharded(), defer=True,
                             trace=(tr, "nyx", 0, 5))
            d = deferred_delta(s0)
            assert d == {"bytes_d2h": 0, "snapshots_deferred": 1,
                         "deferred_fetched": 0}, (kind, d)
            readers = [f.view()["/d"] for _ in range(2)]
            gate = threading.Barrier(2)
            got = [None, None]

            def read(i):
                ds = readers[i]
                gate.wait()
                if kind == "whole":
                    got[i] = np.array(ds[:])
                elif kind == "slab":
                    got[i] = np.array(ds.slab_view((40, 2, 0),
                                                   (8, 12, 16))[:])
                elif kind == "private_copy":
                    ds[0] = -1.0
                    got[i] = np.array(ds[:])
                else:
                    got[i] = np.array(File.load(ds.parent.save(
                        tempfile.mkdtemp()))["/d"][:])

            s0 = transport_stats().snapshot()
            ts = [threading.Thread(target=read, args=(i,)) for i in range(2)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            d = deferred_delta(s0)
            assert d == {"bytes_d2h": g.nbytes, "snapshots_deferred": 0,
                         "deferred_fetched": 1}, (kind, d)
            want = g
            if kind == "slab":
                want = g[40:48, 2:14]
            elif kind == "private_copy":
                want = g.copy()
                want[0] = -1.0
            for got_i in got:
                np.testing.assert_array_equal(got_i, want)
            d2h = [s for s in tr.spans() if s["ph"] == "X"
                   and s["name"] == "datamodel.d2h"]
            assert len(d2h) == 2 and {s["step"] for s in d2h} == {5}, d2h
            assert {s["args"]["device_id"] for s in d2h} == {
                devs[0].id, devs[1].id}, d2h

    def deferred_deleted():
        x = sharded()
        ds = Dataset("d", x.shape, x.dtype, data=x, defer=True)
        x.delete()
        try:
            ds[:]
        except ValueError as e:
            assert "deleted or donated" in str(e), e
        else:
            raise AssertionError("a deleted array was read")

    out = {}
    for case in (sharded_axis0, sharded_traced, replicated, mesh_2x2,
                 write_after, slab_in_shard, slab_across_shards,
                 views_assemble_once, mesh_2x2_slab, write_after_slab,
                 spill_round_trip, one_device, one_device_write,
                 deferred_reads, deferred_deleted):
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


@pytest.mark.parametrize("case", [
    "sharded_axis0", "sharded_traced", "replicated", "mesh_2x2",
    "write_after", "slab_in_shard", "slab_across_shards",
    "views_assemble_once", "mesh_2x2_slab", "write_after_slab",
    "spill_round_trip", "one_device", "one_device_write",
    "deferred_reads", "deferred_deleted"])
def test_sharded_device_snapshot(sharded_snapshots, case):
    """On four CPU devices: a field sharded on axis 0 is kept as its fetched
    shards, with no host copy at write (a d2h span per shard under the
    snapshot span); a slab inside one shard is a view of that shard; a read
    of the whole, a slab across shards, a write or a spill assembles the
    array once per snapshot (an assemble span per shard, on the write's
    step), shared by every view; a fully replicated array and an array on
    one device are one block, adopted and never assembled; a (2, 2) mesh
    fetches each distinct block once; a write through the Dataset leaves
    the device array and sibling slabs as they were.  A deferred snapshot
    fetches nothing at write; the first of two concurrent readers (whole,
    slab, write, spill) fetches each shard once, and a deleted array
    raises."""
    assert sharded_snapshots[case] == "ok", sharded_snapshots[case]
