"""End-to-end behaviour tests for the Wilkins workflow system (the paper)."""

import threading
import time

import numpy as np
import pytest

from repro.core import h5, Wilkins, WorkflowGraph


def _grid(t, n=100):
    return np.arange(n, dtype=np.uint64) + t


PIPELINE_YAML = """
tasks:
  - func: producer
    nprocs: 4
    outports:
      - filename: outfile.h5
        dsets:
          - {name: /group1/grid, memory: 1}
          - {name: /group1/particles, memory: 1}
  - func: consumer1
    nprocs: 2
    inports:
      - filename: outfile.h5
        dsets:
          - {name: /group1/grid, memory: 1}
  - func: consumer2
    nprocs: 2
    inports:
      - filename: outfile.h5
        dsets:
          - {name: /group1/particles, memory: 1}
"""


def test_listing1_three_task_workflow():
    """Paper Listing 1: 1 producer, 2 consumers, per-dataset channels."""
    seen = {"c1": [], "c2": []}

    def producer():
        for t in range(3):
            with h5.File("outfile.h5", "w") as f:
                f.create_dataset("/group1/grid", data=_grid(t))
                f.create_dataset("/group1/particles",
                                 data=np.full((50, 3), t, np.float32))

    def consumer1():
        while True:
            f = h5.File("outfile.h5", "r")
            if f is None:
                break
            assert "/group1/grid" in f
            assert "/group1/particles" not in f  # data-centric selection
            seen["c1"].append(int(f["/group1/grid"][0]))

    def consumer2():
        f = h5.File("outfile.h5", "r")
        if f is None:
            return
        assert "/group1/particles" in f and "/group1/grid" not in f
        seen["c2"].append(float(f["/group1/particles"][0, 0]))

    w = Wilkins(PIPELINE_YAML, {"producer": producer, "consumer1": consumer1,
                                "consumer2": consumer2})
    rep = w.run(timeout=60)
    assert seen["c1"] == [0, 1, 2]        # stateful consumer: launched once
    assert seen["c2"] == [0.0, 1.0, 2.0]  # stateless: relaunched per datum
    assert rep.total_served == 6
    assert rep.task_launches[("consumer2", 0)] >= 3


def test_same_code_standalone(tmp_path):
    """Ease-of-adoption contract: identical task code runs standalone."""
    h5.set_standalone_dir(str(tmp_path))
    try:
        def producer():
            with h5.File("outfile.h5", "w") as f:
                f.create_dataset("/group1/grid", data=_grid(7))

        def consumer():
            f = h5.File("outfile.h5", "r")
            return np.asarray(f["/group1/grid"][:])

        producer()  # no workflow: writes a real container file
        got = consumer()
        np.testing.assert_array_equal(got, _grid(7))
    finally:
        h5.set_standalone_dir(".")


def test_file_transport_spill(tmp_path):
    """The ``file: 1`` transport path spills through disk."""
    yaml = """
tasks:
  - func: p
    outports:
      - filename: out.h5
        dsets:
          - {name: /d, file: 1, memory: 0}
  - func: c
    inports:
      - filename: out.h5
        dsets:
          - {name: /d, file: 1, memory: 0}
"""
    got = []

    def p():
        with h5.File("out.h5", "w") as f:
            f.create_dataset("/d", data=np.arange(10.0))

    def c():
        f = h5.File("out.h5", "r")
        if f is not None:
            got.append(np.asarray(f["/d"][:]))

    w = Wilkins(yaml, {"p": p, "c": c}, spill_dir=str(tmp_path))
    w.run(timeout=30)
    assert len(got) == 1
    np.testing.assert_array_equal(got[0], np.arange(10.0))


def test_ensemble_fanin_round_robin():
    """Paper Listing 2 / Fig 3: 4 producers x 2 consumers, round-robin."""
    yaml = """
tasks:
  - func: producer
    taskCount: 4
    outports:
      - filename: outfile.h5
        dsets: [{name: /group1/grid, memory: 1}]
  - func: consumer
    taskCount: 2
    nprocs: 5
    inports:
      - filename: outfile.h5
        dsets: [{name: /group1/grid, memory: 1}]
"""
    g = WorkflowGraph.from_yaml(yaml)
    assert len(g.edges) == 1
    links = g.edges[0].instance_links(4, 2)
    assert links == [(0, 0), (1, 1), (2, 0), (3, 1)]  # Fig 3 exactly

    lock = threading.Lock()
    got = {0: 0, 1: 0}

    def producer():
        with h5.File("outfile.h5", "w") as f:
            f.create_dataset("/group1/grid", data=_grid(0))

    def consumer(comm):
        while True:
            f = h5.File("outfile.h5", "r")
            if f is None:
                break
            with lock:
                got[comm.instance] += 1

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    w.run(timeout=60)
    assert got == {0: 2, 1: 2}  # each consumer serves 2 producers


@pytest.mark.parametrize("topology,np_,nc", [("fan-out", 1, 4), ("NxN", 3, 3)])
def test_ensemble_topologies(topology, np_, nc):
    yaml = f"""
tasks:
  - func: producer
    taskCount: {np_}
    outports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
  - func: consumer
    taskCount: {nc}
    inports:
      - filename: o.h5
        dsets: [{{name: /g, memory: 1}}]
"""
    def producer():
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=_grid(1))

    n_recv = []
    lock = threading.Lock()

    def consumer():
        while True:
            f = h5.File("o.h5", "r")
            if f is None:
                break
            with lock:
                n_recv.append(1)

    w = Wilkins(yaml, {"producer": producer, "consumer": consumer})
    assert w.graph.topology_kind() == topology
    w.run(timeout=60)
    assert len(n_recv) == max(np_, nc)


def test_subset_writers():
    """Paper §3.2.2 (LAMMPS idiom): io_proc/nwriters restricts I/O ranks."""
    yaml = """
tasks:
  - func: sim
    nprocs: 32
    nwriters: 1
    outports:
      - filename: dump.h5
        dsets: [{name: /particles/*, memory: 1}]
  - func: detector
    nprocs: 8
    inports:
      - filename: dump.h5
        dsets: [{name: /particles/*, memory: 1}]
"""
    w = Wilkins(yaml, {"sim": lambda: None, "detector": lambda: None})
    vol = w.vols[("sim", 0)]
    assert vol.io_procs == 1 and vol.nprocs == 32
    comm = w._make_comm("sim", 0)
    assert comm.is_io_proc(0) and not comm.is_io_proc(1)


def test_custom_actions_nyx_idiom(tmp_path):
    """Paper Listing 5: double open/close custom I/O via action script."""
    script = tmp_path / "actions.py"
    script.write_text("""
def nyx(vol, rank):
    def afc_cb(f):
        if vol.file_close_counter % 2 == 1:
            vol.clear_files()  # 1st close: single-rank metadata I/O, don't serve
        else:
            vol.serve_all(True, True)
            vol.clear_files()
            vol.broadcast_files()
    def bfo_cb(name):
        pass
    vol.set_after_file_close(afc_cb)
    vol.set_before_file_open(bfo_cb)
""")
    yaml = """
tasks:
  - func: nyx
    nprocs: 4
    actions: ["actions", "nyx"]
    outports:
      - filename: plt*.h5
        dsets: [{name: /level_0/density, memory: 1}]
  - func: reeber
    nprocs: 2
    inports:
      - filename: plt*.h5
        dsets: [{name: /level_0/density, memory: 1}]
"""
    received = []

    def nyx():
        for t in range(2):
            # first close: metadata-only (single-process small I/O)
            with h5.File(f"plt{t:05d}.h5", "w") as f:
                f.create_dataset("/level_0/density", data=np.zeros(4))
            # second close: bulk parallel write -> serve
            with h5.File(f"plt{t:05d}.h5", "w") as f:
                f.create_dataset("/level_0/density", data=np.full(64, float(t)))

    def reeber():
        while True:
            f = h5.File("plt*.h5", "r")
            if f is None:
                break
            received.append(float(f["/level_0/density"][0]))

    w = Wilkins(yaml, {"nyx": nyx, "reeber": reeber},
                action_dirs=[str(tmp_path)])
    w.run(timeout=60)
    # only the second (bulk) close of each timestep was served
    assert received == [0.0, 1.0]


def test_fault_tolerance_restart():
    """Driver restarts a failing task instance within the restart budget."""
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] == 1:
            raise RuntimeError("injected failure")
        with h5.File("o.h5", "w") as f:
            f.create_dataset("/g", data=_grid(0))

    got = []

    def consumer():
        f = h5.File("o.h5", "r")
        if f is not None:
            got.append(1)

    yaml = """
tasks:
  - func: flaky
    outports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
  - func: consumer
    inports:
      - filename: o.h5
        dsets: [{name: /g, memory: 1}]
"""
    w = Wilkins(yaml, {"flaky": flaky, "consumer": consumer}, max_restarts=2)
    rep = w.run(timeout=30)
    assert attempts["n"] == 2
    assert len(rep.failures) == 1
    assert got == [1]


def test_cycle_topology():
    """Cycles are a supported directed topology (computational steering)."""
    yaml = """
tasks:
  - func: sim
    outports:
      - filename: state.h5
        dsets: [{name: /x, memory: 1}]
    inports:
      - filename: steer.h5
        dsets: [{name: /param, memory: 1}]
  - func: steer
    inports:
      - filename: state.h5
        dsets: [{name: /x, memory: 1}]
    outports:
      - filename: steer.h5
        dsets: [{name: /param, memory: 1}]
"""
    g = WorkflowGraph.from_yaml(yaml)
    assert len(g.edges) == 2  # sim->steer and steer->sim

    steps = {"sim": [], "steer": []}

    def sim():
        x = 1.0
        for t in range(3):
            with h5.File("state.h5", "w") as f:
                f.create_dataset("/x", data=np.array([x]))
            f = h5.File("steer.h5", "r")
            if f is None:
                break
            x = float(f["/param"][0])
            steps["sim"].append(x)

    def steer():
        while True:
            f = h5.File("state.h5", "r")
            if f is None:
                break
            x = float(f["/x"][0])
            steps["steer"].append(x)
            with h5.File("steer.h5", "w") as g2:
                g2.create_dataset("/param", data=np.array([x * 2]))

    w = Wilkins(yaml, {"sim": sim, "steer": steer})
    w.run(timeout=60)
    assert steps["sim"] == [2.0, 4.0, 8.0]  # steering doubled each step


# ---------------------------------------------------------------------------
# failure paths: error chaining + partial report
# ---------------------------------------------------------------------------
def test_run_failure_chains_secondary_errors_and_attaches_report():
    """Every failing task's error is reachable from the raised exception
    (__context__ chain), and the partial WorkflowReport rides on it."""
    from repro.core.driver import WorkflowReport

    yaml = """
tasks:
  - func: a
  - func: b
"""

    def a():
        raise ValueError("boom-a")

    def b():
        time.sleep(0.05)
        raise KeyError("boom-b")

    w = Wilkins(yaml, {"a": a, "b": b})
    with pytest.raises((ValueError, KeyError)) as ei:
        w.run(timeout=30)
    err = ei.value
    kinds, e = set(), err
    while e is not None:
        kinds.add(type(e))
        e = e.__context__
    assert {ValueError, KeyError} <= kinds   # no error silently discarded
    rep = err.report
    assert isinstance(rep, WorkflowReport)
    assert rep.wall_time_s > 0
    assert {f.error for f in rep.failures} == \
        {"ValueError: boom-a", "KeyError: 'boom-b'"}


def test_run_timeout_attaches_partial_report_and_secondary_errors():
    """The join-deadline TimeoutError no longer discards the report, and a
    task error raised before the hang stays chained on it."""
    yaml = """
tasks:
  - func: hang
  - func: fail
"""
    release = threading.Event()

    def hang():
        release.wait(5.0)

    def fail():
        raise RuntimeError("early failure")

    w = Wilkins(yaml, {"hang": hang, "fail": fail})
    with pytest.raises(TimeoutError) as ei:
        w.run(timeout=0.3)
    release.set()
    err = ei.value
    assert "wilkins-hang-0" in str(err)
    rep = err.report                       # partial report, not discarded
    assert rep.channels == [] or rep.channels is w.channels
    assert [f.error for f in rep.failures] == ["RuntimeError: early failure"]
    kinds, e = set(), err
    while e is not None:
        kinds.add(type(e))
        e = e.__context__
    assert RuntimeError in kinds


NYX_REEBER_YAML = """
tasks:
  - func: nyx
    nprocs: 1024
    outports:
      - filename: plt*.h5
        dsets: [{name: /level_0/density, memory: 1}]
  - func: reeber
    nprocs: 64
    taskCount: 2
    inports:
      - filename: plt*.h5
        dsets: [{name: /level_0/density, memory: 1}]
"""


@pytest.mark.parametrize("n_dev, want", [
    (4, {("nyx", 0): [0, 1], ("reeber", 0): [2], ("reeber", 1): [3]}),
    (8, {("nyx", 0): [0, 1, 2, 3, 4, 5], ("reeber", 0): [6],
         ("reeber", 1): [7]}),
    # fewer devices than instances: disjoint is impossible, the instances
    # past the last device share it
    (2, {("nyx", 0): [0], ("reeber", 0): [1], ("reeber", 1): [1]}),
])
def test_device_groups_are_disjoint_proportional_slices(n_dev, want):
    funcs = {"nyx": lambda: None, "reeber": lambda: None}
    devices = [f"d{i}" for i in range(n_dev)]
    w = Wilkins(NYX_REEBER_YAML, funcs, devices=devices)
    got = {k: [devices.index(d) for d in g] for k, g in w.device_groups.items()}
    assert got == want
    if n_dev >= len(want):
        flat = [d for g in got.values() for d in g]
        assert sorted(flat) == list(range(n_dev))  # disjoint and complete
