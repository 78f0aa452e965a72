"""The four-chip cell on four virtual CPU devices, at the tiny size: nyx's
field sharded over two devices, a reeber instance on each of the other two,
the sharded snapshot assembled on the host and counted, and ``correct``
catching a planted fault.  Run in a child process, which gets its four
devices from ``XLA_FLAGS`` before JAX starts."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench_tiny import BENCH, ROOT

CELL = "cosmo_all.4chip"

CHILD = textwrap.dedent("""
    import json, sys, tempfile, time
    sys.path.insert(0, sys.argv[1])
    import bench_tiny
    from bench_tiny import CPU_LINES, copy_bench, faults, harness
    import jax
    from repro.core.driver import Wilkins

    groups = {}
    partition = Wilkins._partition_devices

    def noting(self, devices):
        out = partition(self, devices)
        groups.update({f"{t}[{i}]": [d.id for d in ds]
                       for (t, i), ds in out.items()})
        return out

    Wilkins._partition_devices = noting
    bench_dir, benchmark = copy_bench(tempfile.mkdtemp(prefix="tiny4_"))
    devices = jax.devices()[:4]
    out = {"groups": groups, "devices": [d.id for d in devices]}
    for name, trace, fault in (("end_to_end", False, "none"),
                               ("traced", True, "none"),
                               ("altered", False, "altered")):
        with faults.plant(fault):
            out[name] = harness.run_cell(
                sys.argv[2], 2**31 + 41, 0.25, trace, devices,
                time.monotonic(), bench_dir=bench_dir, benchmark=benchmark,
                device_lines=CPU_LINES)
    cfg = harness.load_json(bench_dir, "configs", harness.find_cell(
        harness.load_benchmark(benchmark), sys.argv[2])["config"])
    out["shape"] = cfg["shape"]
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", CHILD,
                        os.path.join(BENCH, "tests"), CELL], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    line = [s for s in p.stdout.splitlines() if s.startswith("RESULT ")]
    assert line, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("trace", ["end_to_end", "traced"])
def test_four_device_cell_matches_reference(four, trace):
    res = four[trace]
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"mismatched", "wrong_box", "missing",
                                  "unexpected", "duplicated"}
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 4


def test_four_device_groups(four):
    d = four["devices"]
    assert four["groups"] == {"nyx[0]": d[:2], "reeber[0]": [d[2]],
                              "reeber[1]": [d[3]]}


def test_four_device_snapshot_is_assembled_and_counted(four):
    m = {k: v["value"] for k, v in four["traced"]["metrics"].items()}
    n = 1
    for s in four["shape"]:
        n *= s
    assert m["copy_MiB_per_step"] == n * 4 / 2**20
    assert m["assemble_ms_per_step"] > 0


def test_four_device_planted_fault_makes_run_incorrect(four):
    res = four["altered"]
    assert not res["correct"] and res["failed"] > 0
    assert res["checks"]["mismatched"]["value"] > 0
