"""Run-wide span tracing, critical-path analysis, flight recorder (PR 10).

Covers the ISSUE-10 satellite matrix:

* **zero-cost default** -- an untraced run constructs no ``SpanRecorder``
  (process-wide construction counter) and leaves every hook reference
  ``None`` after teardown;
* **layer coverage** -- a traced fault-injected run records spans from the
  vol / channel / prefetch / reshard / checkpoint / recovery layers;
* **Perfetto round-trip** -- ``export_trace`` -> ``load_trace`` inverts
  exactly (categories, coordinates, flow pairs);
* **critical-path attribution** -- synthetic spans with a known answer,
  per-instance buckets summing to the window exactly, and a 2-edge
  disparate-rate workflow whose slow edge dominates the blocked time;
* **flight recorder** -- a dump accompanies the chained error on all four
  failure paths (terminal task failure, restart exhaustion, stall
  declaration, join timeout);
* **span lifecycle** -- crash/restart and rescale runs leave only closed
  spans, with aborted intervals flagged, and the rebuilt channels/VOLs
  keep recording after the surgery;
* **counter consistency** -- ``Channel.stats_snapshot`` reads under the
  owning lock; the error-path report still carries transport/plan-cache
  snapshots; the vol mux-wait scope never double-counts nested get waits.
"""

import json
import threading
import time

import numpy as np
import pytest

from repro.core import FaultSpec, Wilkins, h5, world
from repro.core.channel import (_in_mux_wait_scope, enter_mux_wait_scope,
                                exit_mux_wait_scope)
from repro.obs import (SpanRecorder, TraceConfig, attribute, critical_path,
                       export_trace, flow_id, format_report, load_trace,
                       per_edge, span_categories, to_chrome)
from repro.obs.recorder import created_count

STEPS = 4
N = 64


# ---------------------------------------------------------------------------
# workflows
# ---------------------------------------------------------------------------
TRACED_YAML = """
tasks:
  - func: producer
    taskCount: 2
    on_failure:
      restart: {max_retries: 2}
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    taskCount: 2
    nprocs: 2
    on_failure:
      restart: {max_retries: 2}
    inports:
      - filename: o.h5
        redistribute: 1
        prefetch: 2
        dsets: [{name: /g, memory: 1}]
"""


def _producer(comm):
    start = 0
    r = comm.restore({"t": np.zeros((), np.int64)})
    if r is not None:
        start = int(r[1]["t"])
    for t in range(start, STEPS):
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(N, dtype=np.float64) + t)
        comm.checkpoint({"t": np.array(t + 1, np.int64)})


def _consumer(comm):
    n = 0
    r = comm.restore({"n": np.zeros((), np.int64)})
    if r is not None:
        n = int(r[1]["n"])
    while True:
        f = h5.File("o.h5", "r")
        if f is None:
            break
        comm.reshard(f["/g"])
        n += 1
        comm.checkpoint({"n": np.array(n, np.int64)})


def _traced_workflow(tmp_path, tag):
    return Wilkins(TRACED_YAML, {"producer": _producer,
                                 "consumer": _consumer},
                   spill_dir=str(tmp_path / tag))


# ---------------------------------------------------------------------------
# TraceConfig parsing / validation
# ---------------------------------------------------------------------------
def test_traceconfig_spellings():
    assert TraceConfig.from_yaml(None) is None
    assert TraceConfig.from_yaml(False) is None
    assert TraceConfig.from_yaml(True).flight_len == 256
    c = TraceConfig.from_yaml({"path": "t.json", "flight_len": 8,
                               "max_spans": 100, "shards": 4})
    assert (c.path, c.flight_len, c.max_spans, c.shards) == \
           ("t.json", 8, 100, 4)
    assert TraceConfig.coerce(None) is None
    assert TraceConfig.coerce("x.json").path == "x.json"
    assert TraceConfig.coerce(c) is c


@pytest.mark.parametrize("doc, err", [
    ({"bogus": 1}, "unknown tracing keys"),
    ({"shards": 3}, "power of two"),
    ({"flight_len": 0}, "flight_len"),
    ({"max_spans": 0}, "max_spans"),
    ("nope", "boolean or a mapping"),
])
def test_traceconfig_rejects(doc, err):
    with pytest.raises(ValueError, match=err):
        TraceConfig.from_yaml(doc)


def test_yaml_tracing_block_parses():
    from repro.core import WorkflowGraph
    g = WorkflowGraph.from_yaml("""
tasks:
  - func: p
tracing: {flight_len: 16}
""")
    assert g.tracing is not None and g.tracing.flight_len == 16


# ---------------------------------------------------------------------------
# zero-cost default
# ---------------------------------------------------------------------------
def test_untraced_run_allocates_no_recorder(tmp_path):
    w = _traced_workflow(tmp_path, "off")
    n0 = created_count()
    rep = w.run(timeout=60)
    assert created_count() == n0, "untraced run constructed a SpanRecorder"
    assert rep.trace_spans == 0 and rep.trace_path is None
    assert rep.critical_path == {} and rep.flight_recorder == []
    for vol in w.vols.values():
        assert vol.tracer is None
    for ch in w.channels:
        assert ch._tracer is None
    assert w._run_tracer is None


# ---------------------------------------------------------------------------
# layer coverage + export round-trip on a fault-injected run
# ---------------------------------------------------------------------------
def test_traced_faulted_run_covers_six_layers(tmp_path):
    w = _traced_workflow(tmp_path, "layers")
    path = str(tmp_path / "trace.json")
    rep = w.run(timeout=60, trace=path,
                faults=FaultSpec(task="consumer", point="recv", step=1,
                                 instance=1))
    assert rep.trace_path == path and rep.trace_spans > 0
    assert len(rep.restarts) == 1
    spans = load_trace(path)
    cats = set(span_categories(spans))
    assert {"vol", "channel", "prefetch", "reshard", "checkpoint",
            "recovery"} <= cats, cats
    # teardown symmetry: tracer detached everywhere after the run
    for vol in w.vols.values():
        assert vol.tracer is None
    for ch in w.channels:
        assert ch._tracer is None

    # the Perfetto document is structurally loadable: metadata tracks,
    # duration events, paired flow arrows, instants, counters
    doc = json.load(open(path))
    phs = {ev["ph"] for ev in doc["traceEvents"]}
    assert {"M", "X", "s", "f", "i"} <= phs, phs
    assert doc["otherData"]["exporter"] == "repro.obs"

    # round-trip: flow arrows pair producer offers with consumer receives
    offers = {s["flow"][1] for s in spans
              if s["flow"] and s["flow"][0] == "s"}
    recvs = {s["flow"][1] for s in spans
             if s["flow"] and s["flow"][0] == "f"}
    assert offers and offers & recvs

    # every span is closed; aborted intervals are flagged, not dangling
    for s in spans:
        assert s["t1"] >= s["t0"]
    # the injected crash aborts the consumer's blocked get
    aborted = [s for s in spans if (s["args"] or {}).get("aborted")]
    assert all(s["args"].get("why") in ("timeout", "interrupt", "poison",
                                        None) or True for s in aborted)

    # summary carries the attribution tables
    text = rep.summary()
    assert "critical-path attribution" in text
    assert "per-edge hand-off costs" in text
    assert f"trace: spans={rep.trace_spans}" in text


def test_export_roundtrip_exact(tmp_path):
    rec = SpanRecorder(TraceConfig(shards=1))
    t = rec.t_origin
    rec.record("channel", "channel.offer", "p", 0, t, t + 0.5, step=3,
               flow=("s", flow_id("e", 3)), edge="e", bytes=64)
    rec.record("channel", "channel.get", "c", 1, t + 0.2, t + 0.6,
               flow=("f", flow_id("e", 3)), edge="e")
    rec.instant("recovery", "task.drop", "c", 1, t=t + 0.7, reason="x")
    rec.counter("qdepth:e", 2, t=t + 0.3)
    path = str(tmp_path / "rt.json")
    export_trace(path, rec)
    back = load_trace(path)
    assert [s["name"] for s in back] == \
           ["channel.offer", "channel.get", "qdepth:e", "task.drop"]
    offer, get = back[0], back[1]
    assert offer["flow"] == ("s", flow_id("e", 3))
    assert get["flow"] == ("f", flow_id("e", 3))
    assert offer["task"] == "p" and offer["instance"] == 0
    assert offer["step"] == 3 and offer["args"]["bytes"] == 64
    assert abs((offer["t1"] - offer["t0"]) - 0.5) < 1e-5
    assert back[2]["args"]["value"] == 2


# ---------------------------------------------------------------------------
# critical-path attribution
# ---------------------------------------------------------------------------
def _span(cat, name, task, inst, t0, t1, **args):
    return {"ph": "X", "cat": cat, "name": name, "task": task,
            "instance": inst, "t0": t0, "t1": t1, "step": args.pop("step", None),
            "flow": None, "args": args or None}


BUCKETS = ("block", "copy", "prep", "reshard", "checkpoint", "recovery",
           "rescale", "compute")


def test_attribution_synthetic_known_answer():
    spans = [
        # window [0, 10]; block [1, 4]; a snapshot [4.5, 7.5] with its
        # device->host fetch nested in it (copy, claimed after block and
        # before reshard); reshard [3, 5] (block and copy claim [3, 4] and
        # [4.5, 5] first -> reshard nets 0.5s); checkpoint [8, 9]
        _span("channel", "channel.get", "c", 0, 1.0, 4.0, edge="e"),
        _span("datamodel", "datamodel.snapshot", "c", 0, 4.5, 7.5),
        _span("datamodel", "datamodel.d2h", "c", 0, 4.5, 7.0),
        _span("reshard", "reshard.numpy", "c", 0, 3.0, 5.0, edge=None),
        _span("checkpoint", "ckpt.save", "c", 0, 8.0, 9.0),
        _span("task", "task.window", "c", 0, 0.0, 10.0),
    ]
    rep = attribute(spans)
    row = rep["instances"]["c[0]"]
    assert row["window_s"] == pytest.approx(10.0)
    assert row["block"] == pytest.approx(3.0)
    assert row["copy"] == pytest.approx(3.0)
    assert row["reshard"] == pytest.approx(0.5)
    assert row["checkpoint"] == pytest.approx(1.0)
    assert row["compute"] == pytest.approx(2.5)
    total = sum(row[b] for b in BUCKETS)
    assert total == pytest.approx(row["window_s"], abs=1e-12)
    assert critical_path(spans) == "c[0]"
    text = format_report(rep)
    assert "c[0] *" in text


def test_attribution_vol_lifecycle_claims_nothing():
    spans = [
        # vol.close CONTAINS a nested offer wait: only the wait may claim
        _span("vol", "vol.close", "p", 0, 0.0, 5.0),
        _span("channel", "channel.offer", "p", 0, 1.0, 3.0, edge="e"),
    ]
    row = attribute(spans)["instances"]["p[0]"]
    assert row["block"] == pytest.approx(2.0)
    assert row["compute"] == pytest.approx(3.0)


def test_per_edge_rollup_separates_prep_from_blocked():
    spans = [
        _span("prefetch", "prefetch.prep", "pool", 3, 0.0, 1.0, edge="e",
              bytes=100),
        _span("prefetch", "prefetch.wait", "c", 0, 2.0, 2.5, edge="e",
              cache="miss", bytes=100),
        _span("channel", "channel.get", "c", 0, 3.0, 3.25, edge="e"),
        _span("reshard", "reshard.pack", "c", 0, 4.0, 4.1, edge="f",
              cache="hit", bytes=7),
    ]
    edges = per_edge(spans)
    assert edges["e"]["prep_s"] == pytest.approx(1.0)
    assert edges["e"]["blocked_s"] == pytest.approx(0.75)
    assert edges["e"]["bytes"] == 200 and edges["e"]["misses"] == 1
    assert edges["f"]["hits"] == 1 and edges["f"]["bytes"] == 7


def test_disparate_rate_attribution(tmp_path):
    """2-edge fan-in with one slow producer: the consumer's blocked time
    lands on the slow edge, and the fast producer blocks in its offers --
    a known answer the analyzer must reproduce from the spans alone."""
    yaml = """
tasks:
  - func: slow
    outports: [{filename: a.h5, dsets: [{name: /g, memory: 1}]}]
  - func: fast
    outports: [{filename: b.h5, dsets: [{name: /h, memory: 1}]}]
  - func: sink
    inports:
      - {filename: a.h5, dsets: [{name: /g, memory: 1}]}
      - {filename: b.h5, dsets: [{name: /h, memory: 1}]}
"""
    delay = 0.05

    def slow():
        for t in range(STEPS):
            time.sleep(delay)
            with h5.File("a.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(8.0) + t)

    def fast():
        for t in range(STEPS):
            with h5.File("b.h5", "w") as f:
                f.create_dataset("/h", data=np.arange(8.0) - t)

    def sink():
        while True:
            fa = h5.File("a.h5", "r")
            if fa is None:
                break
            h5.File("b.h5", "r")

    w = Wilkins(yaml, {"slow": slow, "fast": fast, "sink": sink},
                spill_dir=str(tmp_path / "rate"))
    rep = w.run(timeout=60, trace=True)
    att = rep.critical_path
    assert att["instances"]
    for key, row in att["instances"].items():
        total = sum(row[b] for b in BUCKETS)
        assert total == pytest.approx(row["window_s"], abs=1e-9), key
    edges = att["edges"]
    slow_edge = next(e for e in edges if "a.h5" in e)
    fast_edge = next(e for e in edges if "b.h5" in e)
    # the sink spends most of the run waiting for the slow producer; the
    # fast edge's handoffs are nearly instant by comparison
    assert edges[slow_edge]["blocked_s"] > 2 * delay
    assert edges[slow_edge]["blocked_s"] > edges[fast_edge]["blocked_s"]
    # the slow producer is the critical path; most of its window is compute
    # (the sleeps), not blocking
    crit = att["critical"]
    assert crit.startswith(("slow", "sink"))
    # per-step rows exist on the critical instance and sum to latency
    for step, row in att["steps"].items():
        total = sum(row[b] for b in BUCKETS)
        assert total == pytest.approx(row["latency_s"], rel=0.05), step


# ---------------------------------------------------------------------------
# flight recorder: all four failure paths
# ---------------------------------------------------------------------------
FAIL_YAML = """
tasks:
  - func: p
    outports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
  - func: c
    %s
    inports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
"""


def _p3():
    for t in range(3):
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=np.arange(8.0) + t)


def test_flight_dump_on_terminal_task_failure(tmp_path):
    def c():
        h5.File("o.h5", "r")
        raise RuntimeError("dies immediately")

    w = Wilkins(FAIL_YAML % "", {"p": _p3, "c": c},
                spill_dir=str(tmp_path / "fail"))
    with pytest.raises(RuntimeError) as ei:
        w.run(timeout=60, trace=True)
    rep = ei.value.report
    assert rep.flight_recorder, "no flight dump on terminal failure"
    d = rep.flight_recorder[0]
    assert d["task"] == "c" and "task failure" in d["reason"]
    assert d["spans"], "dump carries no recent spans"
    assert "FLIGHT-DUMP" in rep.summary()


def test_flight_dump_on_restart_exhaustion(tmp_path):
    def c(comm):
        h5.File("o.h5", "r")
        raise RuntimeError("dies every attempt")

    w = Wilkins(FAIL_YAML % "on_failure: {restart: {max_retries: 1}}",
                {"p": _p3, "c": c}, spill_dir=str(tmp_path / "exh"))
    with pytest.raises(RuntimeError) as ei:
        w.run(timeout=60, trace=True)
    rep = ei.value.report
    assert any("restarts exhausted" in d["reason"]
               for d in rep.flight_recorder), rep.flight_recorder
    # exactly one dump for the one terminal error (no double-dump from the
    # runner's generic handler)
    assert len(rep.flight_recorder) == 1


def test_flight_dump_on_stall(tmp_path):
    yaml = """
tasks:
  - func: p1
    outports: [{filename: a.h5, dsets: [{name: /g, memory: 1}]}]
    on_failure: {restart: {max_retries: 3}}
  - func: c1
    taskCount: 2
    stall_timeout_s: 0.25
    inports:
      - {filename: a.h5, redistribute: 1, dsets: [{name: /g, memory: 1}]}
    on_failure: {rescale: {nslots: 1, max_retries: 3}}
"""
    from repro.core import world
    from repro.core.redistribute import even_blocks

    def p1(comm):
        comm.restore({"t": np.zeros((), np.int64)})
        for t in range(STEPS):
            with h5.File("a.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(16.0) + t)
            comm.checkpoint({"t": np.array(t + 1, np.int64)})

    def c1(comm):
        spec = comm.resolve_redist_spec(port="a.h5")
        _, shape = even_blocks((16,), spec.nslots)[spec.slot]
        state = {"acc": np.zeros(shape, np.float64),
                 "n": np.zeros((), np.int64)}
        r = comm.restore(state)
        if r is not None:
            state = r[1]
        acc, n = np.asarray(state["acc"]).copy(), int(state["n"])
        while True:
            f = h5.File("a.h5", "r")
            if f is None:
                break
            acc = acc + f["/g"][...]
            n += 1
            comm.checkpoint({"acc": acc, "n": np.array(n, np.int64)},
                            sharded_axes={"acc": 0})

    w = Wilkins(yaml, {"p1": p1, "c1": c1}, spill_dir=str(tmp_path / "st"))
    path = str(tmp_path / "stall.json")
    rep = w.run(timeout=60, trace=path,
                faults=FaultSpec(task="c1", kind="stall", point="recv",
                                 step=1, instance=0, seconds=1.5))
    assert len(rep.stalls) == 1
    assert any("stall declared" in d["reason"] for d in rep.flight_recorder)
    # the rescale surgery the stall triggered left its stage spans, and the
    # rebuilt channels kept recording afterwards
    spans = load_trace(path)
    stages = {s["name"] for s in spans if s["cat"] == "rescale"}
    assert {"rescale.grace", "rescale.snapshot", "rescale.recut",
            "rescale.rebuild", "rescale.swap"} <= stages, stages
    t_swap = max(s["t1"] for s in spans if s["name"] == "rescale.swap")
    # the new edge emits queue-depth samples and the new VOL emits mux
    # waits as the replayed steps drain into the resized consumer
    assert any(s["cat"] in ("vol", "counter") and s["t0"] >= t_swap
               for s in spans), \
        "rebuilt channels/VOLs recorded nothing after the surgery"


def test_flight_dump_on_join_timeout(tmp_path):
    ev = threading.Event()

    def hang(comm):
        ev.wait(10)

    w = Wilkins("tasks:\n  - func: hang\n", {"hang": hang},
                spill_dir=str(tmp_path / "hang"))
    try:
        with pytest.raises(TimeoutError) as ei:
            w.run(timeout=0.3, trace=True)
    finally:
        ev.set()
    rep = ei.value.report
    assert any("join timeout" in d["reason"] for d in rep.flight_recorder)


def test_flight_ring_is_bounded():
    rec = SpanRecorder(TraceConfig(flight_len=8, shards=1, max_spans=10))
    for i in range(100):
        rec.record("task", "t", "a", 0, float(i), float(i) + 0.5)
    assert len(rec.flight()) == 8
    assert len(rec) == 10 and rec.dropped == 90
    for i in range(20):
        rec.mark_failure(f"r{i}")
    assert len(rec.dumps()) == 8  # bounded dump list


# ---------------------------------------------------------------------------
# span lifecycle under crash/restart/rescale
# ---------------------------------------------------------------------------
def test_restart_spans_closed_and_marked(tmp_path):
    w = _traced_workflow(tmp_path, "life")
    path = str(tmp_path / "life.json")
    rep = w.run(timeout=60, trace=path,
                faults=FaultSpec(task="producer", point="close", step=1,
                                 instance=0))
    assert len(rep.restarts) == 1
    spans = load_trace(path)
    assert all(s["t1"] >= s["t0"] for s in spans)
    assert any(s["name"] == "recovery.restart" for s in spans)
    assert any(s["name"] == "channel.quarantine_producer" for s in spans)
    # post-restart generation kept recording: serves continue after the
    # restart span closes
    t_restart = max(s["t1"] for s in spans
                    if s["name"] == "recovery.restart")
    assert any(s["name"] == "channel.offer" and s["t0"] >= t_restart
               for s in spans), "no spans recorded after the restart"


# ---------------------------------------------------------------------------
# counter consistency
# ---------------------------------------------------------------------------
def test_channel_stats_snapshot_locked(tmp_path):
    w = _traced_workflow(tmp_path, "snap")
    w.run(timeout=60)
    for ch in w.channels:
        snap = ch.stats_snapshot()
        assert snap["served"] == ch.stats.served
        assert snap["bytes_moved"] == ch.stats.bytes_moved
        for k, v in snap.items():
            assert isinstance(v, (int, float)), (k, type(v))


def test_error_report_carries_transport_snapshots(tmp_path):
    def c():
        h5.File("o.h5", "r")
        raise RuntimeError("boom")

    w = Wilkins(FAIL_YAML % "", {"p": _p3, "c": c},
                spill_dir=str(tmp_path / "errsnap"))
    with pytest.raises(RuntimeError) as ei:
        w.run(timeout=60)
    rep = ei.value.report
    assert rep.transport, "error-path report lost the transport snapshot"
    assert rep.plan_cache, "error-path report lost the plan-cache snapshot"
    assert rep.scheduler


def test_mux_wait_scope_prevents_double_count():
    from repro.core.channel import Channel
    from repro.core.datamodel import File

    def mk():
        return Channel(name="p[0]->c[0]:o.h5", producer=("p", 0),
                       consumer=("c", 0), filename_pattern="o.h5",
                       dset_patterns=["/g"], io_freq=1, queue_depth=2,
                       prefetch=0, record_events=False)

    ch = mk()
    f = File("o.h5")
    f.create_dataset("/g", data=np.zeros(4))
    ch.offer(f)
    # inside the vol's mux-wait scope, get() must NOT add consumer_wait_s
    # (the vol accounts the scan wait itself); outside it must
    token = enter_mux_wait_scope([ch])
    try:
        assert _in_mux_wait_scope(ch)
        assert ch.get() is not None
        assert ch.stats.consumer_wait_s == 0.0
    finally:
        exit_mux_wait_scope(token)
    assert not _in_mux_wait_scope(ch)
    ch2 = mk()
    f2 = File("o.h5")
    f2.create_dataset("/g", data=np.zeros(4))
    ch2.offer(f2)
    assert ch2.get() is not None
    assert ch2.stats.consumer_wait_s > 0.0


def test_mux_wait_not_double_counted_end_to_end(tmp_path):
    """The report-level invariant: one slow producer, one consumer waiting
    through the vol mux.  The consumer's per-edge wait must be counted
    once -- consumer_wait_s stays at the same order as the wall time, not
    2x (the pre-fix behaviour double-counted mux + nested get waits)."""
    delay = 0.08
    yaml = """
tasks:
  - func: p
    outports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
  - func: c
    inports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
"""

    def p():
        for t in range(3):
            time.sleep(delay)
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=np.arange(4.0) + t)

    def c():
        while True:
            if h5.File("o.h5", "r") is None:
                break

    w = Wilkins(yaml, {"p": p, "c": c}, spill_dir=str(tmp_path / "mux"))
    rep = w.run(timeout=60)
    wait = sum(ch.stats.consumer_wait_s for ch in w.channels)
    assert wait <= rep.wall_time_s + 0.01, \
        f"consumer_wait_s {wait:.3f} exceeds wall {rep.wall_time_s:.3f}"
    assert wait >= 2 * delay * 0.5, f"mux waits not accounted: {wait:.4f}"


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------
def test_obs_report_cli(tmp_path, capsys):
    rec = SpanRecorder(TraceConfig(shards=1))
    t = rec.t_origin
    rec.record("channel", "channel.offer", "p", 0, t, t + 0.2,
               step=0, edge="e")
    rec.record("channel", "channel.get", "c", 0, t + 0.1, t + 0.3,
               step=0, edge="e")
    path = str(tmp_path / "cli.json")
    export_trace(path, rec)
    from repro.obs.__main__ import main
    assert main(["report", path]) == 0
    out = capsys.readouterr().out
    assert "critical-path attribution" in out
    assert "spans" in out
    assert main(["report", path, "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "instances" in doc and "edges" in doc


def test_obs_report_cli_empty_trace(tmp_path, capsys):
    path = str(tmp_path / "empty.json")
    json.dump({"traceEvents": []}, open(path, "w"))
    from repro.obs.__main__ import main
    assert main(["report", path]) == 1


# ---------------------------------------------------------------------------
# spans on the profiler's clock: span(), annotations, the snapshot spans
# ---------------------------------------------------------------------------
@pytest.fixture
def annotations(monkeypatch):
    """Every ``wilkins/`` profiler annotation opened, by name, with how many
    are open still (``jax.profiler.TraceAnnotation`` counted)."""
    import jax.profiler

    real = jax.profiler.TraceAnnotation
    seen = {"opened": [], "open": 0}

    class Counting:
        def __init__(self, name, **kw):
            self.name = name
            self._inner = real(name, **kw)

        def __enter__(self):
            if self.name.startswith("wilkins/"):
                seen["opened"].append(self.name)
                seen["open"] += 1
            return self._inner.__enter__()

        def __exit__(self, *exc):
            if self.name.startswith("wilkins/"):
                seen["open"] -= 1
            return self._inner.__exit__(*exc)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    return seen


def test_span_nests_parents_and_marks_aborted(annotations):
    rec = SpanRecorder(TraceConfig(shards=1))
    with rec.span("vol", "vol.close", "p", 0, step=3, filename="o.h5"):
        with rec.span("datamodel", "datamodel.snapshot", "p", 0, step=3,
                      bytes=8) as args:
            args["extra"] = 1
            assert annotations["open"] == 2
        rec.record("channel", "channel.offer", "p", 0, 0.0, 0.0, step=3)
        with pytest.raises(ValueError):
            with rec.span("datamodel", "datamodel.d2h", "p", 0, step=3):
                raise ValueError("fetch failed")
    assert annotations["open"] == 0
    assert annotations["opened"] == ["wilkins/vol.close",
                                     "wilkins/datamodel.snapshot",
                                     "wilkins/datamodel.d2h"]
    by = {s["name"]: s for s in rec.spans()}
    close = by["vol.close"]
    assert close["parent"] is None and close["step"] == 3
    for child in ("datamodel.snapshot", "channel.offer", "datamodel.d2h"):
        assert by[child]["parent"] == close["id"], child
    assert len({s["id"] for s in by.values()}) == 4
    assert by["datamodel.snapshot"]["args"] == {"bytes": 8, "extra": 1}
    assert by["datamodel.d2h"]["args"] == {"aborted": True}
    assert "aborted" not in close["args"]
    assert close["t0"] <= by["datamodel.snapshot"]["t0"] <= \
        by["datamodel.snapshot"]["t1"] <= close["t1"]


def test_span_step_set_inside_the_block():
    rec = SpanRecorder(TraceConfig(shards=1))
    with rec.span("checkpoint", "ckpt.save", "p", 0) as args:
        args["step"] = 7
    (s,) = rec.spans()
    assert s["step"] == 7 and s["args"] is None


def test_parents_are_per_thread():
    rec = SpanRecorder(TraceConfig(shards=2))
    inside = threading.Event()
    done = threading.Event()

    def other():
        inside.wait(10)
        rec.record("channel", "channel.get", "c", 0, 0.0, 0.0)
        done.set()

    th = threading.Thread(target=other)
    th.start()
    with rec.span("vol", "vol.close", "p", 0):
        inside.set()
        assert done.wait(10)
    th.join(10)
    get = next(s for s in rec.spans() if s["name"] == "channel.get")
    assert get["parent"] is None


def _device_workflow(tmp_path, tag):
    import jax.numpy as jnp

    yaml = """
tasks:
  - func: p
    outports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
  - func: c
    inports:
      - {filename: o.h5, redistribute: 1, prefetch: 1,
         dsets: [{name: /g, memory: 1}]}
"""

    def p():
        for t in range(3):
            with h5.File("o.h5", "w") as f:
                f.create_dataset("/g", data=jnp.arange(32.0) + t)

    def c():
        while h5.File("o.h5", "r") is not None:
            pass

    return Wilkins(yaml, {"p": p, "c": c}, spill_dir=str(tmp_path / tag))


def test_untraced_run_constructs_no_annotation(tmp_path, annotations):
    w = _device_workflow(tmp_path, "off")
    n0 = created_count()
    w.run(timeout=60)
    assert created_count() == n0
    assert annotations["opened"] == []


def test_traced_run_times_the_snapshot(tmp_path, annotations):
    from repro.core.datamodel import transport_stats

    w = _device_workflow(tmp_path, "on")
    path = str(tmp_path / "snap.json")
    d2h0 = transport_stats().snapshot()["bytes_d2h"]
    w.run(timeout=60, trace=path)
    assert transport_stats().snapshot()["bytes_d2h"] - d2h0 == 3 * 32 * 4
    spans = load_trace(path)
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s)
    snaps, d2hs = by["datamodel.snapshot"], by["datamodel.d2h"]
    assert [s["step"] for s in snaps] == [0, 1, 2]
    assert [s["step"] for s in d2hs] == [0, 1, 2]
    ids = {s["id"]: s for s in snaps}
    for d in d2hs:
        parent = ids[d["parent"]]
        assert parent["t0"] <= d["t0"] <= d["t1"] <= parent["t1"]
        assert d["args"]["bytes"] == 32 * 4 and d["task"] == "p"
    assert all(s["args"]["device"] is True and s["args"]["bytes"] == 32 * 4
               for s in snaps)
    # the copy bucket takes the snapshot out of the producer's compute
    att = attribute(spans)
    assert att["instances"]["p[0]"]["copy"] > 0
    # every X span the program recorded had its profiler annotation
    names = {f"wilkins/{s['name']}" for s in spans if s["ph"] == "X"}
    assert {"wilkins/vol.close", "wilkins/vol.open.wait",
            "wilkins/channel.offer", "wilkins/prefetch.prep",
            "wilkins/prefetch.wait", "wilkins/datamodel.snapshot",
            "wilkins/datamodel.d2h"} <= names
    assert names <= set(annotations["opened"])
    assert annotations["open"] == 0


def test_prefetch_spans_carry_the_handoff(tmp_path):
    w = _device_workflow(tmp_path, "flow")
    path = str(tmp_path / "flow.json")
    w.run(timeout=60, trace=path)
    spans = load_trace(path)
    offers = {s["flow"][1]: s for s in spans if s["name"] == "channel.offer"}
    assert len(offers) == 3
    for name in ("prefetch.prep", "prefetch.wait"):
        got = [s for s in spans if s["name"] == name]
        assert len(got) == 3, name
        for s in got:
            role, fid = s["flow"]
            assert role == "t" and fid in offers, name
            assert s["step"] is not None
    waits = sorted(s["step"] for s in spans if s["name"] == "prefetch.wait")
    assert waits == [0, 1, 2]
    doc = json.load(open(path))
    assert "t" in {ev["ph"] for ev in doc["traceEvents"]}


@pytest.mark.parametrize("data, d2h", [("device", 64), ("host", 0)],
                         ids=["device", "host"])
def test_bytes_d2h_counts_device_arrays_only(data, d2h):
    import jax.numpy as jnp

    from repro.core.datamodel import File, transport_stats

    arr = jnp.ones(16, jnp.float32) if data == "device" else \
        np.ones(16, np.float32)
    s0 = transport_stats().snapshot()
    File("x.h5").create_dataset("/g", data=arr)
    s1 = transport_stats().snapshot()
    assert s1["bytes_copied"] - s0["bytes_copied"] == 64
    assert s1["bytes_d2h"] - s0["bytes_d2h"] == d2h


def test_traced_reshard_ends_at_the_device_work(monkeypatch):
    import jax
    import jax.numpy as jnp

    from repro.core.comm import TaskComm
    from repro.core.redistribute import RedistSpec

    real = jax.block_until_ready
    calls = []
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(x) or real(x))
    g = jnp.asarray(np.arange(37 * 8, dtype=np.float32).reshape(37, 8))
    spec = RedistSpec(axis=0, nslots=2, slot=1, nranks=2)
    untraced = TaskComm().reshard(g, spec, prefer="pack")
    assert calls == []
    rec = SpanRecorder(TraceConfig(shards=1))
    traced = TaskComm(task="c", tracer=rec).reshard(g, spec, prefer="pack")
    assert len(calls) == 1 and calls[0] is traced
    for a, b in zip(untraced, traced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    (s,) = rec.spans()
    assert s["name"] == "reshard.pack"
    assert s["args"] == {"bytes": 37 * 8 * 4, "ranks": len(traced)}


def test_export_roundtrip_carries_ids_and_steps_of_a_flow(tmp_path):
    rec = SpanRecorder(TraceConfig(shards=1))
    t = rec.t_origin
    fid = flow_id("e", 1)
    with rec.span("vol", "vol.close", "p", 0, step=0):
        rec.record("channel", "channel.offer", "p", 0, t, t + 0.1, step=0,
                   flow=("s", fid), edge="e")
    rec.record("prefetch", "prefetch.wait", "c", 0, t + 0.2, t + 0.3,
               step=0, flow=("t", fid), edge="e")
    path = str(tmp_path / "ids.json")
    export_trace(path, rec)
    back = {s["name"]: s for s in load_trace(path)}
    orig = {s["name"]: s for s in rec.spans()}
    for name in orig:
        assert back[name]["id"] == orig[name]["id"]
        assert back[name]["parent"] == orig[name]["parent"]
        assert back[name]["flow"] == orig[name]["flow"]
    assert back["channel.offer"]["parent"] == back["vol.close"]["id"]


def test_merge_timeline_keeps_instants_only(tmp_path):
    from repro.obs import merge_timeline

    class Timeline:
        def events(self):
            return [{"t": 1.0, "kind": "restart", "task": "c",
                     "instance": 0}, {"t": 2.0, "kind": "tick"}]

        def samples(self):
            return [{"t": 1.5, "edge": "e", "queue_len": 1, "inflight": 0}]

    out = merge_timeline(Timeline())
    assert [(s["ph"], s["name"]) for s in out] == [("i", "timeline.restart")]
    # the queue-depth and in-flight tracks come from the channel hooks
    w = _device_workflow(tmp_path, "tracks")
    path = str(tmp_path / "tracks.json")
    w.run(timeout=60, trace=path)
    tracks = {s["name"].split(":")[0] for s in load_trace(path)
              if s["ph"] == "C"}
    assert {"qdepth", "inflight"} <= tracks
