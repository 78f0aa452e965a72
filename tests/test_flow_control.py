"""Flow-control semantics (paper §3.6, Table 2 behaviour)."""

import time

import numpy as np
import pytest

from repro.core import h5, Wilkins
from repro.core.channel import Channel, FlowControl


def test_io_freq_decoding():
    assert FlowControl.from_io_freq(0) == (FlowControl.ALL, 1)
    assert FlowControl.from_io_freq(1) == (FlowControl.ALL, 1)
    assert FlowControl.from_io_freq(5) == (FlowControl.SOME, 5)
    assert FlowControl.from_io_freq(-1) == (FlowControl.LATEST, 1)
    with pytest.raises(ValueError):
        FlowControl.from_io_freq(-3)


def _run_workflow(io_freq, n_steps=6, consumer_sleep=0.05):
    yaml = f"""
tasks:
  - func: producer
    outports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    inports:
      - filename: o.h5
        io_freq: {io_freq}
        dsets: [{{name: /g, memory: 1}}]
"""
    got = []

    def producer():
        for t in range(n_steps):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.array([t]))

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            time.sleep(consumer_sleep)
            got.append(int(f["/g"][0]))

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    return got, rep


def test_flow_control_all():
    got, rep = _run_workflow(io_freq=1)
    assert got == [0, 1, 2, 3, 4, 5]      # every timestep served
    assert rep.total_dropped == 0


def test_flow_control_some():
    got, rep = _run_workflow(io_freq=2)
    assert got == [1, 3, 5]               # every 2nd close served
    assert rep.total_dropped == 3


def test_flow_control_some_n5():
    got, rep = _run_workflow(io_freq=5, n_steps=10)
    assert got == [4, 9]
    assert rep.total_dropped == 8


def test_flow_control_latest_drops_when_consumer_busy():
    got, rep = _run_workflow(io_freq=-1, n_steps=8, consumer_sleep=0.15)
    # only timesteps where the consumer was already waiting are served; the
    # rest are dropped at zero cost -- exact counts are timing-dependent.
    assert rep.total_dropped > 0
    assert got == sorted(got)             # in-order, never stale reordering
    assert len(got) + rep.total_dropped == 8


def test_flow_control_reduces_producer_wait():
    """The paper's Table 2 effect: 'some' saves producer idle time."""
    _, rep_all = _run_workflow(io_freq=1, n_steps=6, consumer_sleep=0.08)
    _, rep_some = _run_workflow(io_freq=3, n_steps=6, consumer_sleep=0.08)
    wait_all = sum(c.stats.producer_wait_s for c in rep_all.channels)
    wait_some = sum(c.stats.producer_wait_s for c in rep_some.channels)
    assert wait_some < wait_all


def test_gantt_events_recorded():
    yaml = """
tasks:
  - func: p
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: c
    inports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
"""
    def p():
        for t in range(2):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.array([t]))

    def c():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break

    w = Wilkins(yaml, {"p": p, "c": c}, record_events=True)
    rep = w.run(timeout=30)
    events = rep.gantt_events()
    kinds = {e[3] for e in events}
    assert "serve" in kinds and "recv" in kinds  # Fig 5 reconstruction data


# ---------------------------------------------------------------------------
# waiter accounting (latest rendezvous fan-in)
# ---------------------------------------------------------------------------
def test_waiter_accounting_dedupes_mux_and_get():
    """A consumer the VOL mux marked waiting that then blocks in ``get`` on
    the same channel is ONE waiter, not two registrations."""
    import threading

    from repro.core.channel import ChannelTimeout

    ch = Channel("w", ("p", 0), ("c", 0), "o.h5", ["/g"], io_freq=-1)
    observed = []
    registered = threading.Event()

    def consumer():
        ch.set_consumer_waiting(True)   # the VOL mux marks us...
        registered.set()
        try:
            with pytest.raises(ChannelTimeout):
                ch.get(timeout=0.5)     # ...then the same thread blocks in get
        finally:
            ch.set_consumer_waiting(False)

    th = threading.Thread(target=consumer)
    th.start()
    assert registered.wait(2.0)
    deadline = time.monotonic() + 2.0
    while time.monotonic() < deadline and th.is_alive():
        observed.append(ch.waiting_consumers())
        time.sleep(0.02)
    th.join()
    assert max(observed) == 1           # never double-counted
    assert ch.waiting_consumers() == 0  # balanced after both exits


def test_latest_fanin_rendezvous():
    """2 producers -> 1 `latest` consumer through the VOL mux: data arrives
    fresh and in per-producer order, and waiter accounting drains to zero."""
    yaml = """
tasks:
  - func: producer
    taskCount: 2
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    inports:
      - filename: o.h5
        io_freq: -1
        dsets: [{name: /g, memory: 1}]
"""
    got = []

    def producer(comm):
        for t in range(5):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.array([comm.instance * 100 + t]))
            time.sleep(0.02)

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            got.append(int(f["/g"][0]))

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    rep = w.run(timeout=60)
    assert rep.total_served + rep.total_dropped == 10
    assert rep.total_served == len(got)
    for inst in (0, 1):
        mine = [g for g in got if g // 100 == inst]
        assert mine == sorted(mine)     # never stale reordering per producer
    assert all(c.waiting_consumers() == 0 for c in rep.channels)


# ---------------------------------------------------------------------------
# a close every channel will drop keeps a device array unfetched
# ---------------------------------------------------------------------------
def _device_field(t):
    return np.arange(24, dtype=np.float32).reshape(4, 6) + 100.0 * t


def _run_device_workflow(io_freq, n_steps=10, producer=None,
                         consumer_sleep=0.0):
    """A producer that writes a ``jax.Array`` each step; the consumer
    keeps every delivered value, read ``consumer_sleep`` after it came.  Returns (steps, values, report, the
    deferral counters' change over the run)."""
    import jax.numpy as jnp

    from repro.core.datamodel import transport_stats

    yaml = f"""
tasks:
  - func: producer
    outports:
      - filename: o*.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    inports:
      - filename: o*.h5
        io_freq: {io_freq}
        dsets: [{{name: /g, memory: 1}}]
"""
    got = []

    def writes():
        for t in range(n_steps):
            with h5.File(f"o{t}.h5", "w") as f:
                f.create_dataset("/g", data=jnp.asarray(_device_field(t)))

    def consumer():
        while True:
            f = h5.File("o*.h5", "r")
            if f is None:
                break
            time.sleep(consumer_sleep)
            got.append(np.array(f["/g"][:]))

    s0 = transport_stats().snapshot()
    w = Wilkins(yaml, {"producer": producer or writes, "consumer": consumer})
    rep = w.run(timeout=60)
    s1 = transport_stats().snapshot()
    delta = {k: s1[k] - s0[k] for k in
             ("bytes_d2h", "snapshots_deferred", "deferred_fetched")}
    steps = [int(round(g[0, 0] / 100.0)) for g in got]
    for s, g in zip(steps, got):
        np.testing.assert_array_equal(g, _device_field(s))
    return steps, rep, delta


def test_some_edge_fetches_only_served_snapshots():
    steps, rep, delta = _run_device_workflow(io_freq=5, n_steps=10)
    assert steps == [4, 9]
    assert rep.total_dropped == 8
    assert delta == {"bytes_d2h": 2 * _device_field(0).nbytes,
                     "snapshots_deferred": 8, "deferred_fetched": 0}
    assert rep.transport["snapshots_deferred"] >= 8


@pytest.mark.parametrize("io_freq", [1, -1])
def test_all_and_latest_edges_defer_nothing(io_freq):
    steps, rep, delta = _run_device_workflow(io_freq=io_freq, n_steps=6)
    if io_freq == 1:
        assert steps == list(range(6))
    assert delta == {"bytes_d2h": 6 * _device_field(0).nbytes,
                     "snapshots_deferred": 0, "deferred_fetched": 0}


def test_custom_close_action_defers_nothing():
    """An ``after_file_close`` action decides its own serve timing, so the
    write keeps its eager fetch even where the channel drops the close."""
    import jax.numpy as jnp

    from repro.core.vol import current_vol

    def producer():
        vol = current_vol()

        def serve(f):
            vol.serve_all(True, True)
            vol.clear_files()

        vol.set_after_file_close(serve)
        for t in range(10):
            with h5.File(f"o{t}.h5", "w") as f:
                f.create_dataset("/g", data=jnp.asarray(_device_field(t)))

    steps, rep, delta = _run_device_workflow(io_freq=5, producer=producer)
    assert steps == [4, 9]
    assert delta == {"bytes_d2h": 10 * _device_field(0).nbytes,
                     "snapshots_deferred": 0, "deferred_fetched": 0}


def test_forecast_miss_is_fetched_before_close_returns():
    """Two matching files open at once: both forecast a drop at write, the
    second close is served all the same.  Its snapshot is fetched before
    ``close`` returns, so donating the array afterwards cannot reach it."""
    import jax
    import jax.numpy as jnp

    donate = jax.jit(lambda a: a * 0.0, donate_argnums=0)

    def producer():
        for t in range(0, 6, 2):
            xa = jnp.asarray(_device_field(t))
            xb = jnp.asarray(_device_field(t + 1))
            fa = h5.File(f"o{t}.h5", "w")
            fb = h5.File(f"o{t + 1}.h5", "w")
            fa.create_dataset("/g", data=xa)
            fb.create_dataset("/g", data=xb)
            fa.close()   # the close count's odd step: dropped
            fb.close()   # the even one: served, though forecast a drop
            donate(xa)
            donate(xb)
            assert xa.is_deleted()   # nothing was fetched from it

    # the consumer reads after the producer has donated
    steps, rep, delta = _run_device_workflow(io_freq=2, producer=producer,
                                             consumer_sleep=0.05)
    assert steps == [1, 3, 5]
    assert delta == {"bytes_d2h": 3 * _device_field(0).nbytes,
                     "snapshots_deferred": 6, "deferred_fetched": 3}


@pytest.mark.parametrize("io_freq", list(range(-1, 13)))
def test_forecast_agrees_with_offer(io_freq):
    """``drops_next_close`` says what ``offer`` then does, for every close
    count up to 12 (``latest`` is never forecast, and drops here with no
    consumer waiting); an abandoned channel drops every close."""
    from repro.core.datamodel import File

    ch = Channel("f", ("p", 0), ("c", 0), "o.h5", ["/g"],
                 io_freq=io_freq, queue_depth=64)
    for _ in range(13):
        forecast = ch.drops_next_close()
        served = ch.offer(File("o.h5"))
        if ch.strategy == FlowControl.LATEST:
            assert not forecast and not served
        else:
            assert forecast == (not served)
    ch.abandon_consumer()
    assert ch.drops_next_close() and not ch.offer(File("o.h5"))


def test_dropped_close_lets_its_device_array_go():
    """A dropped file's unfetched array is let go at its close, by
    reference count alone (the file's tree is a reference cycle, so the
    garbage collector would free it late); a read after that close is an
    error that names the cause, while a read before it fetches."""
    import gc
    import weakref

    import jax.numpy as jnp

    seen = {}

    def producer():
        was = gc.isenabled()
        gc.disable()
        try:
            for t in range(5):
                x = jnp.asarray(_device_field(t))
                ref = weakref.ref(x)
                with h5.File(f"o{t}.h5", "w") as f:
                    f.create_dataset("/g", data=x)
                    if t == 1:   # read inside the block: fetched
                        np.testing.assert_array_equal(
                            f["/g"][:], _device_field(t))
                del x
                seen[t] = ref() is None
                if t == 0:
                    with pytest.raises(ValueError, match="let go"):
                        f["/g"][:]
        finally:
            if was:
                gc.enable()

    steps, rep, delta = _run_device_workflow(io_freq=5, n_steps=5,
                                             producer=producer)
    assert steps == [4]
    assert seen == {t: True for t in range(5)}   # fetched or let go
    assert delta == {"bytes_d2h": 2 * _device_field(0).nbytes,
                     "snapshots_deferred": 4, "deferred_fetched": 1}
