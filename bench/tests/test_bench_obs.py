"""The readings of the program's own spans and counters (``repro.obs``):
the snapshot's device->host fetch, its bytes, the hand-off, and the
``wilkins/`` profiler annotations beside the harness's own."""

import math

import pytest

from bench_tiny import CPU_LINES, cells, copy_bench, harness, run
from bench import trace_reduce


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return copy_bench(str(tmp_path_factory.mktemp("tiny_obs")))


def _snapshot_bytes(cfg):
    """Bytes one producer step hands ``create_dataset`` as device arrays."""
    if "shape" in cfg:
        return math.prod(cfg["shape"]) * 4                  # f32 field
    return int(cfg["points_per_process"]) * int(cfg["producer_nprocs"]) * (
        2 * 4 + 3 * 4)                                      # grid + particles


@pytest.mark.parametrize("cell", cells())
def test_traced_run_reads_the_snapshot_and_the_handoff(tiny, cell):
    bench_dir, _ = tiny
    res = run(tiny, cell, seed=2**31 + 29, trace=True)
    assert res["correct"], res["checks"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    spec = harness.find_cell(harness.load_benchmark(), cell)
    cfg = harness.load_json(bench_dir, "configs", spec["config"])
    assert m["d2h_MiB_per_step"] == _snapshot_bytes(cfg) / 2**20
    assert 0 < m["d2h_ms_per_step"] <= m["vol_ms_per_step"]
    assert ("handoff_ms_per_delivery" in m) == (cell == "cosmo_all")
    if cell == "cosmo_all":
        assert m["handoff_ms_per_delivery"] > 0


def test_program_annotations_sit_beside_the_harness_labels(tmp_path):
    """A CPU profile of a traced workflow holds ``wilkins/`` annotations on
    the task threads, nested in the harness's ``bench/`` ones; the
    reduction reads its labels from the ``bench/`` annotations alone."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core import Wilkins, h5

    yaml = """
tasks:
  - func: p
    outports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
  - func: c
    inports: [{filename: o.h5, dsets: [{name: /g, memory: 1}]}]
"""
    f = jax.jit(lambda x, t: jnp.sin(x) * t)
    x = jnp.ones((256, 256))
    f(x, 0.0).block_until_ready()

    def p():
        with jax.profiler.TraceAnnotation("bench/traced"):
            for t in range(3):
                with jax.profiler.TraceAnnotation("bench/p.step"):
                    y = f(x, float(t)).block_until_ready()
                with jax.profiler.TraceAnnotation("bench/p.write"), \
                        h5.File("o.h5", "w") as fh:
                    fh.create_dataset("/g", data=y)

    def c():
        while h5.File("o.h5", "r") is not None:
            pass

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        Wilkins(yaml, {"p": p, "c": c},
                spill_dir=str(tmp_path / "spill")).run(timeout=60, trace=True)
    finally:
        jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(str(tmp_path))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                for e in line.events:
                    lines.setdefault(e.name, set()).add((plane.name, k))
    for name in ("wilkins/datamodel.d2h", "wilkins/datamodel.snapshot",
                 "wilkins/vol.close", "wilkins/channel.offer",
                 "wilkins/vol.open.wait"):
        assert name in lines, name
    # the producer's spans run on the thread of its bench/ annotations
    for name in ("wilkins/datamodel.d2h", "wilkins/vol.close",
                 "wilkins/channel.offer"):
        assert lines[name] == lines["bench/p.write"], name
    devices, ann = trace_reduce.parse(path, CPU_LINES)
    assert ann and all(n.startswith("bench/") for n, *_ in ann)
    out = trace_reduce.reduce_events(devices, ann)
    assert out is not None
    assert all("wilkins" not in label for label, _ in out["idle_gaps"])
