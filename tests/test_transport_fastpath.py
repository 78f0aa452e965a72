"""Zero-copy transport fast path: CoW views, shared fan-out payloads,
pipelined channels, raw mmap spill container, and ChannelTimeout."""

import os
import threading
import time

import numpy as np
import pytest

from repro.core import h5, Wilkins
from repro.core.channel import (NO_DATA, Channel, ChannelMux, ChannelTimeout)
from repro.core.datamodel import (BlockOwnership, File, compile_path_pattern,
                                  match_path, reset_transport_stats,
                                  transport_stats)
from repro.core.vol import VOL


# ---------------------------------------------------------------------------
# CoW dataset views
# ---------------------------------------------------------------------------
def test_view_shares_memory_until_write():
    f = File("a.h5")
    ds = f.create_dataset("/g/d", data=np.arange(16.0))
    v = ds.view()
    assert np.shares_memory(v.read_direct(), ds.read_direct())
    assert ds.share_count == 2 and v.share_count == 2

    reset_transport_stats()
    v[0] = 99.0  # first write -> exactly one CoW copy
    s = transport_stats().snapshot()
    assert s["cow_copies"] == 1
    assert s["bytes_copied"] == ds.nbytes
    assert not np.shares_memory(v.read_direct(), ds.read_direct())
    assert v[0] == 99.0 and ds[0] == 0.0  # source untouched

    v[1] = 5.0  # second write: already private, no further copy
    assert transport_stats().snapshot()["cow_copies"] == 1


def test_create_dataset_snapshots_caller_array():
    """h5py semantics: the file owns its buffers. A producer reusing one
    scratch array across steps must not corrupt queued payloads."""
    scratch = np.arange(8.0)
    f = File("a.h5")
    ds = f.create_dataset("/d", data=scratch)
    assert not np.shares_memory(ds.read_direct(), scratch)
    scratch[:] = -1.0  # caller mutates their buffer after the close/serve
    assert ds[0] == 0.0


def test_view_write_through_source_also_copies():
    f = File("a.h5")
    ds = f.create_dataset("/d", data=np.zeros(8))
    v = ds.view()
    ds[3] = 7.0  # writer side materializes; the view keeps the old snapshot
    assert ds[3] == 7.0 and v[3] == 0.0


def test_shared_buffer_reads_are_readonly_aliases():
    f = File("a.h5")
    ds = f.create_dataset("/d", data=np.arange(4))
    v = ds.view()
    alias = v.read_direct()
    assert not alias.flags.writeable
    with pytest.raises(ValueError):
        alias[0] = 1


def test_file_view_is_structural_and_zero_copy():
    f = File("x.h5")
    f.attrs["run"] = 1
    d = f.create_dataset("/a/b", data=np.ones((4, 4)))
    d.attrs["t"] = 2
    reset_transport_stats()
    fv = f.view()
    assert transport_stats().snapshot()["bytes_copied"] == 0
    assert np.shares_memory(fv["/a/b"].read_direct(), d.read_direct())
    assert fv.attrs["run"] == 1 and fv["/a/b"].attrs["t"] == 2


# ---------------------------------------------------------------------------
# fan-out shares one payload
# ---------------------------------------------------------------------------
def test_fanout_serves_one_shared_payload():
    """4 channels on one VOL serve ONE filtered payload, no data copies."""
    vol = VOL("producer")
    chans = [
        Channel(f"p->c{i}", ("producer", 0), ("consumer", i), "o.h5", ["/grid"])
        for i in range(4)
    ]
    vol.outgoing.extend(chans)

    f = File("o.h5")
    src = f.create_dataset("/grid", data=np.arange(1000, dtype=np.uint64))

    reset_transport_stats()
    vol.on_file_close(f)
    assert transport_stats().snapshot()["bytes_copied"] == 0

    got = [c.get(timeout=5) for c in chans]
    arrs = [g["/grid"].read_direct() for g in got]
    for a in arrs:
        assert np.shares_memory(a, src.read_direct())
    np.testing.assert_array_equal(arrs[0], np.arange(1000, dtype=np.uint64))


def test_fanout_workflow_consumers_share_memory():
    yaml = """
tasks:
  - func: producer
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    taskCount: 4
    inports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
"""
    lock = threading.Lock()
    received = []

    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(256.0))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            with lock:
                received.append(f["/g"].read_direct())

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    assert len(received) == 4
    for a in received[1:]:
        assert np.shares_memory(received[0], a)


# ---------------------------------------------------------------------------
# raw mmap spill container
# ---------------------------------------------------------------------------
def test_spill_roundtrip_preserves_attrs_and_ownership(tmp_path):
    f = File("snap.h5")
    f.attrs["step"] = 12
    d = f.create_dataset("/grid", data=np.arange(100, dtype=np.uint64))
    d.attrs["timestep"] = 3
    own = BlockOwnership()
    own.add(0, (0,), (50,))
    own.add(1, (50,), (50,))
    d.ownership = own
    f.create_dataset("/p/pos", data=np.ones((10, 3), np.float32))

    path = f.save(str(tmp_path))
    g = File.load(path)
    np.testing.assert_array_equal(g["/grid"][:], np.arange(100, dtype=np.uint64))
    assert g.attrs["step"] == 12
    assert g["/grid"].attrs["timestep"] == 3
    assert g["/grid"].ownership.blocks[1] == ((50,), (50,))
    assert g.total_bytes() == f.total_bytes()


def test_spill_load_is_mmap_backed_and_aligned(tmp_path):
    f = File("snap.h5")
    f.create_dataset("/a", data=np.arange(7, dtype=np.int8))  # odd size
    f.create_dataset("/b", data=np.arange(5, dtype=np.float64))
    path = f.save(str(tmp_path))

    reset_transport_stats()
    g = File.load(path)
    assert transport_stats().snapshot()["bytes_copied"] == 0  # zero-copy load
    assert isinstance(g["/a"].read_direct(), np.memmap) or isinstance(
        g["/a"].read_direct().base, np.memmap)
    np.testing.assert_array_equal(g["/b"][:], np.arange(5, dtype=np.float64))

    # 64-byte segment alignment in the container
    import json
    with open(path, "rb") as fh:
        assert fh.read(8) == b"WLKNRAW1"
        hlen = int.from_bytes(fh.read(8), "little")
        meta = json.loads(fh.read(hlen).decode())
    for info in meta["datasets"].values():
        assert info["offset"] % 64 == 0


def test_spill_load_rejects_unknown_container(tmp_path):
    path = tmp_path / "not_a_spill.h5"
    path.write_bytes(b"PK\x03\x04" + bytes(60))
    with pytest.raises(ValueError, match="not a spill container"):
        File.load(str(path))


def test_spill_roundtrip_empty_and_scalar_datasets(tmp_path):
    f = File("e.h5")
    f.create_dataset("/empty", data=np.zeros((0, 3), np.float32))
    f.create_dataset("/scalar", data=np.float64(7.5), shape=())
    f.create_dataset("/d", data=np.arange(4))
    path = f.save(str(tmp_path))
    g = File.load(path)
    assert g["/empty"].shape == (0, 3)
    assert float(g["/scalar"][()]) == 7.5
    np.testing.assert_array_equal(g["/d"][:], np.arange(4))


def test_spill_loaded_dataset_is_cow_writable(tmp_path):
    f = File("s.h5")
    f.create_dataset("/d", data=np.arange(10.0))
    path = f.save(str(tmp_path))
    g = File.load(path)
    g["/d"][0] = -1.0  # mmap mode="r" buffer -> write triggers CoW copy
    assert g["/d"][0] == -1.0
    h = File.load(path)
    assert h["/d"][0] == 0.0  # container on disk untouched


def test_file_transport_spill_cleans_up(tmp_path):
    """The file:1 path round-trips through the raw container and unlinks."""
    yaml = """
tasks:
  - func: p
    outports:
      - filename: out.h5
        dsets: [{name: /d, file: 1, memory: 0}]
  - func: c
    inports:
      - filename: out.h5
        dsets: [{name: /d, file: 1, memory: 0}]
"""
    got = []

    def p():
        for t in range(3):
            with h5.File("out.h5", "w") as f:
                f.create_dataset("/d", data=np.arange(10.0) + t)

    def c():
        while True:
            f = h5.File("out.h5", "r")
            if f is None:
                break
            got.append(np.asarray(f["/d"][:]))

    w = Wilkins(yaml, {"p": p, "c": c}, spill_dir=str(tmp_path))
    w.run(timeout=30)
    assert len(got) == 3
    np.testing.assert_array_equal(got[2], np.arange(10.0) + 2)
    assert os.listdir(str(tmp_path)) == []  # consumed spills are unlinked


# ---------------------------------------------------------------------------
# queue_depth pipelining
# ---------------------------------------------------------------------------
def _pipeline_yaml(queue_depth):
    return f"""
tasks:
  - func: producer
    outports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    inports:
      - filename: o.h5
        queue_depth: {queue_depth}
        dsets: [{{name: /g, memory: 1}}]
"""


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_queue_depth_serves_all_steps_in_order(depth):
    n = 8
    got = []

    def producer():
        for t in range(n):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.array([t]))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            time.sleep(0.01)
            got.append(int(f["/g"][0]))

    w = Wilkins(_pipeline_yaml(depth), {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    assert got == list(range(n))
    assert rep.total_served == n and rep.total_dropped == 0


def test_queue_depth_pipelines_producer():
    """With depth >= 2 a fast producer runs ahead instead of blocking."""
    ch1 = Channel("d1", ("p", 0), ("c", 0), "o.h5", ["/g"], queue_depth=1)
    ch2 = Channel("d2", ("p", 0), ("c", 0), "o.h5", ["/g"], queue_depth=2)
    f = File("o.h5")
    f.create_dataset("/g", data=np.zeros(4))
    assert ch1.offer(f) and ch2.offer(f)
    assert ch2.offer(f)  # second step queues without any consumer
    assert ch2.peek_pending()
    done = []
    t = threading.Thread(target=lambda: done.append(ch1.offer(f)))
    t.start()
    time.sleep(0.05)
    assert not done  # depth-1 rendezvous: producer is blocked
    assert ch1.get(timeout=5) is not None
    t.join(timeout=5)
    assert done == [True]
    assert ch2.get(timeout=5) is not None and ch2.get(timeout=5) is not None


def test_graph_queue_depth_from_yaml():
    from repro.core.graph import WorkflowGraph

    g = WorkflowGraph.from_yaml(_pipeline_yaml(3))
    assert g.edges[0].queue_depth == 3
    w = Wilkins(_pipeline_yaml(3), {"producer": lambda: None, "consumer": lambda: None})
    assert w.channels[0].queue_depth == 3
    with pytest.raises(ValueError):
        WorkflowGraph.from_yaml(_pipeline_yaml(0))


# ---------------------------------------------------------------------------
# ChannelTimeout + mux
# ---------------------------------------------------------------------------
def test_get_timeout_raises_not_none():
    ch = Channel("t", ("p", 0), ("c", 0), "o.h5", ["/g"])
    t0 = time.monotonic()
    with pytest.raises(ChannelTimeout):
        ch.get(timeout=0.05)
    assert time.monotonic() - t0 >= 0.05
    assert ch.stats.consumer_wait_s > 0  # timeout path is accounted

    ch.finish()
    assert ch.get(timeout=0.05) is None  # producer-done is still None


def test_try_get_sentinels():
    ch = Channel("t", ("p", 0), ("c", 0), "o.h5", ["/g"])
    assert ch.try_get() is NO_DATA
    f = File("o.h5")
    f.create_dataset("/g", data=np.ones(3))
    assert ch.offer(f)
    out = ch.try_get()
    assert out is not NO_DATA and out is not None
    ch.finish()
    assert ch.try_get() is None


def test_mux_no_missed_wakeup():
    mux = ChannelMux()
    token = mux.token()
    mux.notify()  # lands "between scan and wait"
    t0 = time.monotonic()
    assert mux.wait(token, timeout=5) != token
    assert time.monotonic() - t0 < 1.0  # returned immediately, no timeout


def test_fanin_mux_delivers_from_any_channel():
    chans = [Channel(f"p{i}", ("p", i), ("c", 0), "o.h5", ["/g"]) for i in range(3)]
    vol = VOL("c")
    vol.incoming.extend(chans)

    def producer(i, delay):
        time.sleep(delay)
        f = File("o.h5")
        f.create_dataset("/g", data=np.array([i]))
        chans[i].offer(f)
        chans[i].finish()

    threads = [threading.Thread(target=producer, args=(i, 0.02 * (i + 1)))
               for i in range(3)]
    for t in threads:
        t.start()
    got = []
    while True:
        f = vol.on_file_open("o.h5")
        if f is None:
            break
        got.append(int(f["/g"][0]))
    for t in threads:
        t.join()
    assert sorted(got) == [0, 1, 2]


# ---------------------------------------------------------------------------
# satellites: global run deadline, matches_file memo, bounded event ring
# ---------------------------------------------------------------------------
def test_run_timeout_is_one_global_deadline():
    """A hung workflow with many task threads fails after ~timeout, not
    N_threads x timeout (the old per-join bug)."""
    yaml = """
tasks:
  - func: a
    taskCount: 3
  - func: b
    taskCount: 3
"""
    release = threading.Event()

    def hang():
        release.wait(5.0)

    w = Wilkins(yaml, {"a": hang, "b": hang})
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        w.run(timeout=0.3)
    elapsed = time.monotonic() - t0
    release.set()  # let the leaked daemon threads exit promptly
    assert elapsed < 1.5  # 6 threads x 0.3s per-join would be >= 1.8s


def test_matches_file_is_memoized():
    ch = Channel("m", ("p", 0), ("c", 0), "plt*.h5", ["/g"])
    assert ch.matches_file("plt00010.h5")
    assert not ch.matches_file("other.h5")
    assert ch._match_cache == {"plt00010.h5": True, "other.h5": False}
    # memo hit returns the same answer without recompiling the reverse glob
    assert ch.matches_file("plt00010.h5") and not ch.matches_file("other.h5")


def test_event_ring_is_bounded_with_drop_counter():
    ch = Channel("e", ("p", 0), ("c", 0), "o.h5", ["/g"],
                 record_events=True, events_maxlen=8)
    for i in range(20):
        ch._event_locked("producer", f"tick{i}")
    assert len(ch.stats.events) == 8
    assert ch.stats.events_dropped == 12
    # the ring keeps the NEWEST events (oldest roll off)
    assert ch.stats.events[-1][2] == "tick19"
    assert ch.stats.events[0][2] == "tick12"


# ---------------------------------------------------------------------------
# glob matcher cache
# ---------------------------------------------------------------------------
def test_compiled_pattern_cache_hits():
    m1 = compile_path_pattern("/group1/*")
    m2 = compile_path_pattern("/group1/*")
    assert m1 is m2  # LRU-cached compiled matcher
    assert m1.matches("/group1/grid")
    assert m1.matches("/group1/deep/nest")
    assert not m1.matches("/other/grid")
    assert match_path("/group1/*", "/group1/grid")
