"""``trace_reduce`` on hand-built intervals and on a trace recorded here."""

import threading
import time

import pytest

from bench_tiny import CPU_LINES
from bench import trace_reduce as tr


def test_union_gaps_total():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 5)]) == [
        (0, 2.5), (3, 4)]
    assert tr.gaps([(1, 2), (3, 4)], (0, 5)) == [(0, 1), (2, 3), (4, 5)]
    assert tr.gaps([], (0, 5)) == [(0, 5)]
    assert tr.total([(0, 1), (2, 4)]) == 3


def test_labels_take_the_innermost_annotation_per_thread():
    ann = [("bench/traced", "p", 0.0, 10.0), ("bench/nyx.write", "p", 1.0, 3.0),
           ("bench/reeber.h2d", "c0", 2.0, 4.0), ("other", "c1", 0.0, 10.0)]
    assert tr.label_at(2.5, ann) == "nyx.write+other+reeber.h2d"
    assert tr.label_at(5.0, ann) == "other+traced"
    assert tr.label_at(11.0, ann) == "(no annotation)"
    idle = tr.idle_by_label([(1.5, 2.5), (5, 6), (6.5, 7)], ann)
    assert idle == [("other+traced", 1.5), ("nyx.write+other+reeber.h2d", 1.0)]


def test_top_ops_sum_by_module_and_name():
    ops = [("fusion", 0, 1), ("fusion", 2, 4), ("copy", 4.5, 5), ("x", 9, 9.5)]
    mods = [("jit_step", 0, 4.2), ("jit_put", 4.2, 6)]
    assert tr.top_ops(ops, mods) == [("jit_step:fusion", 3), ("jit_put:copy", 0.5),
                                     ("x", 0.5)]


def test_short_names_drop_hlo_text_and_fingerprints():
    assert tr.short_name("%fusion.13 = f32[512,512]{1,0} fusion(f32[512,512] "
                         "%rho), kind=kLoop") == "fusion.13"
    assert tr.short_name("jit_nyx_step(11857026594960603146)") == "jit_nyx_step"
    ops = [("%copy.1 = f32[8] copy(%a)", 0, 1), ("%copy.1 = f32[8] copy(%b)", 1, 2)]
    assert tr.top_ops(ops, [("jit_f(42)", 0, 5)]) == [("jit_f:copy.1", 2)]


def test_reduce_events_averages_busy_over_devices():
    devices = {"d0": {"ops": [("a", 0, 2), ("b", 1, 3), ("c", 8, 12)]},
               "d1": {"ops": [("a", 2, 4)]}}
    ann = [("bench/traced", "p", 1.0, 9.0), ("bench/x.step", "p", 4.0, 6.0)]
    out = tr.reduce_events(devices, ann)
    assert out["window_s"] == 8.0
    assert out["busy_per_device"] == {"d0": 3.0, "d1": 2.0}
    assert out["busy_s"] == 2.5
    # idle gaps, labelled at their midpoints: d0 (3, 8) at 5.5 is in
    # x.step; d1 (1, 2) and (4, 9) at 1.5 and 6.5 are in the window only.
    # Summed by label and averaged over the two devices:
    assert dict(out["idle_gaps"]) == pytest.approx(
        {"x.step": 5 / 2, "traced": (1 + 5) / 2})
    assert tr.reduce_events(devices, [("bench/x.step", "p", 0, 1)]) is None
    assert tr.reduce_events({"d0": {"ops": [("a", 20, 30)]}}, ann) is None


def test_reduce_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)

    def work():
        with jax.profiler.TraceAnnotation("bench/traced"):
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench/x.step"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("bench/x.sleep"):
                    time.sleep(0.02)

    th = threading.Thread(target=work)
    th.start()
    th.join(timeout=60)
    assert not th.is_alive()
    jax.profiler.stop_trace()
    out = tr.reduce_trace(str(tmp_path), CPU_LINES)
    assert out is not None
    assert 0.06 <= out["window_s"] < 30
    assert 0 < out["busy_s"] < out["window_s"]
    labels = dict(out["idle_gaps"])
    assert labels.get("x.sleep", 0) >= 0.05
    assert out["device_ops"] and all(s > 0 for _, s in out["device_ops"])


def test_traced_run_profiles_only_after_its_window(tmp_path, monkeypatch):
    """The window of a traced run is measured with the profiler off; the
    profiled tail follows it, and its steps are no window steps."""
    from bench import harness

    clock = [100.0]
    monkeypatch.setattr(harness.time, "monotonic", lambda: clock[0])
    monkeypatch.setattr(harness, "TRACE_SECONDS", 2.0)
    run = harness.Run(seed=1, cfg={"consumer_instances": 1},
                      traffic={"warmup_steps": 1}, seconds=3.0,
                      trace_dir=str(tmp_path))
    run.warmed.add(0)
    going = []
    for step in range(10):
        going.append(run.keep_going(step))
        if not going[-1]:
            break
        assert run.profiling == (run.t_stop is not None)
        run.close_done[step] = clock[0] + 0.5
        clock[0] += 1.0
    run.stop_profiler()
    # steps 1..3 are the window (3 s), 4..5 the profiled tail (2 s)
    assert (run.step0, run.step1) == (1, 4)
    assert going == [True] * 6 + [False]
    assert run.t_stop == 104.0 and run.t_trace == 104.0
    assert not run.profiling and trace_reduce_found(tmp_path)
    reading = harness.Reading(run=run, setup_s=0.0, every=False, trace=None)
    assert reading.window_steps() == [1, 2, 3]


def trace_reduce_found(path):
    return tr.find_xplane(str(path)) is not None
