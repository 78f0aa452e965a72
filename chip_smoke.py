#!/usr/bin/env python3
"""Bring the in situ coupling up on a TPU chip and check what comes out.

    python chip_smoke.py [--seed N]   # one chip: pack kernels + workflow
    python chip_smoke.py --four-chip  # four chips: device groups only

Every phase runs in this one process (a chip belongs to one process), on
data made on the device from ``--seed``:

* device   -- JAX's platform must be ``tpu``; anything else fails at once.
* kernels  -- ``TaskComm.reshard(..., ranks="all", prefer="pack")`` of a
  512^3 f32 density field along axis 0 into 6 ranks and along axis 1 into
  4, of a (16384, 16384) f32 field along axis 1 into 4, and of a
  (100, 300, 300) field whose row width ends in a partial block.  Each
  block list must be byte-identical to ``redistribute_numpy`` on a host
  copy, no reshard may take the numpy executor, and the lowered pack call
  must hold ``tpu_custom_call`` (Mosaic, not the interpreter).
* workflow -- Nyx + Reeber through ``Wilkins.run`` at 512^3: ``nyx``
  evolves the field for 6 snapshots and writes each through ``h5.File``;
  two ``reeber`` instances read an axis-0 ``redistribute:`` slab each, put
  it on their device, count halos and take a checksum that depends on each
  value's position.  Once under ``io_freq: 1`` (every count and checksum
  must equal nyx's own of that slab, taken straight from its device field)
  and once under ``io_freq: -1`` (every delivered one must, and each
  instance must get the last snapshot).  ``nyx`` also reshards its field
  with the pack kernels once, from inside the task.

``--four-chip`` runs only the workflow over four chips -- ``nyx`` on two
with its field sharded on ``comm.mesh()``, each ``reeber`` on one -- and
checks that the device groups are disjoint, that each task's arrays sit on
its own group, and that the halo counts equal a one-chip run of the same
workflow.

Per-phase wall times, compile counts, the compile cache's size at start and
end, and checks are printed as they happen.  Only when every check passed
is the last line of stdout
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failure exits non-zero without it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
N = 512                    # density field edge: 512^3 f32 = 512 MiB
PLANE = 16384              # 2-D field edge: 1 GiB f32
ODD = (100, 300, 300)      # rows of 90000 f32 end in a partial lane block
SNAPSHOTS = 6
PORT = "plt*.h5"
DSET = "/level_0/density"
WORKFLOW = """
tasks:
  - func: nyx
    nprocs: 1024
    outports:
      - filename: plt*.h5
        dsets: [{{name: /level_0/density, memory: 1}}]
  - func: reeber
    nprocs: 64
    taskCount: 2
    inports:
      - filename: plt*.h5
        io_freq: {io_freq}
        redistribute: {{axis: 0}}
        dsets: [{{name: /level_0/density, memory: 1}}]
"""


def check(ok: bool, what: str) -> None:
    print(f"  check {'ok  ' if ok else 'FAIL'} {what}", flush=True)
    if not ok:
        raise AssertionError(what)


class Compiles:
    """Counts XLA backend compiles (JAX's own monitoring events)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def cache_size(path: str) -> str:
    files = [os.path.join(d, f) for d, _, fs in os.walk(path) for f in fs]
    return (f"{len(files)} files, "
            f"{sum(os.path.getsize(f) for f in files)} bytes")


@functools.cache
def checksum_fn():
    """A uint32 sum of each value's bits weighted by its flat position:
    moving a row or a tile changes it, and being integer it is exact in any
    reduction order, so a sharded field and a one-device slab agree."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def checksum(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        pos = jnp.arange(a.size, dtype=jnp.uint32).reshape(a.shape)
        return jnp.sum(bits * (pos * jnp.uint32(2654435761) + 1),
                       dtype=jnp.uint32)

    return checksum


def same_bytes(got, want) -> bool:
    import numpy as np

    got = np.ascontiguousarray(got)
    return (got.shape == want.shape and got.dtype == want.dtype
            and np.array_equal(got.view(np.uint8), want.view(np.uint8)))


# ----------------------------------------------------------------- kernels
def reshard_case(field, axis: int, nranks: int) -> None:
    import jax
    import numpy as np

    from repro.core.comm import TaskComm
    from repro.core.redistribute import (RedistSpec, execute_pack_jax_all,
                                         plan_cache, redistribute_numpy)

    shape = tuple(field.shape)
    spec = RedistSpec(axis=axis, nslots=1, slot=0, nranks=nranks)
    got = TaskComm(task="chip_smoke").reshard(field, spec, ranks="all",
                                              prefer="pack")
    host = np.asarray(field)
    src = [((0,) * len(shape), shape)]
    dst, _ = spec.dst_boxes(shape)
    want = redistribute_numpy(host, src, dst)
    check(len(got) == len(want) and all(
        same_bytes(np.asarray(g), w) for g, w in zip(got, want)),
        f"reshard {shape} axis {axis} into {nranks} ranks is byte-identical "
        f"to redistribute_numpy")
    plan = plan_cache().get(src, dst, shape, field.dtype)
    text = jax.jit(lambda a: execute_pack_jax_all(plan, a)).lower(
        field).as_text()
    check("tpu_custom_call" in text,
          f"pack call for {shape} axis {axis} lowers to tpu_custom_call")


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.datamodel import reset_transport_stats, transport_stats

    reset_transport_stats()
    key = jax.random.key(seed)
    cube = jax.random.uniform(key, (N, N, N), jnp.float32)
    reshard_case(cube, 0, 6)      # ragged rank count: the tail tile
    reshard_case(cube, 1, 4)
    del cube
    plane = jax.random.uniform(jax.random.fold_in(key, 1), (PLANE, PLANE),
                               jnp.float32)
    reshard_case(plane, 1, 4)
    del plane
    odd = jax.random.uniform(jax.random.fold_in(key, 2), ODD, jnp.float32)
    reshard_case(odd, 0, 6)
    s = transport_stats().snapshot()
    check(s["reshard_numpy"] == 0 and s["reshard_pack"] == 4,
          f"every reshard took the pack kernels (pack={s['reshard_pack']}, "
          f"numpy={s['reshard_numpy']})")


# ----------------------------------------------------------------- workflow
def run_workflow(seed: int, io_freq: int, devices):
    """One Nyx + Reeber run; returns (report, groups, reference, counts,
    placements).  ``reference`` and ``counts`` map (slot, step) to a slab's
    (halo count, checksum)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.bench_cosmo import _halos, _nyx_step
    from repro.core import Wilkins, h5

    checksum = checksum_fn()
    reference = {}     # (slot, step) -> nyx's own device slab's
    counts = {}        # (slot, step) -> what reeber computed
    placements = []    # (task, instance, array, devices it sat on)

    def nyx(comm):
        mesh = comm.mesh()
        shard = NamedSharding(mesh, P("data"))
        key = jax.random.key(seed)
        rho = jax.jit(lambda k: jnp.exp(0.5 * jax.random.normal(
            k, (N, N, N), jnp.float32)), out_shardings=shard)(key)
        slots = comm.resolve_redist_spec(port=PORT).dst_boxes(rho.shape)[1]
        for t in range(SNAPSHOTS):
            rho = _nyx_step(rho, jax.random.fold_in(key, t))
            placements.append((comm.task, comm.instance, "field",
                               rho.devices()))
            for s, (starts, shape) in enumerate(slots):
                slab = rho[starts[0]:starts[0] + shape[0]]
                reference[(s, t)] = (int(_halos(slab)), int(checksum(slab)))
            if t == 0:
                # the pack kernels, from inside the producing task
                blocks = comm.reshard(rho, port=PORT, ranks="all",
                                      prefer="pack")
                placements.append((comm.task, comm.instance, "blocks",
                                   blocks[0].devices()))
                if int(checksum(jnp.concatenate(blocks))) != int(
                        checksum(rho)):
                    raise AssertionError("nyx's pack reshard differs from "
                                         "its field")
            with h5.File(f"plt{t:05d}.h5", "w") as f:
                f.create_dataset(DSET, data=rho).attrs["step"] = t

    def reeber(comm):
        device = comm.mesh().devices.flat[0]
        while True:
            f = h5.File(PORT, "r")
            if f is None:
                return
            ds = f[DSET]
            slab = jax.device_put(ds[:], device)
            counts[(comm.instance, int(ds.attrs["step"]))] = (
                int(_halos(slab)), int(checksum(slab)))
            placements.append((comm.task, comm.instance, "slab",
                               slab.devices()))

    w = Wilkins(WORKFLOW.format(io_freq=io_freq),
                {"nyx": nyx, "reeber": reeber}, devices=devices)
    report = w.run(timeout=600)
    return report, w.device_groups, reference, counts, placements


def check_run(name, report, reference, counts, every: bool) -> None:
    check(not report.failures,
          f"{name}: no task failed ({[str(f) for f in report.failures]})")
    check(not report.dropped_tasks,
          f"{name}: no task dropped ({report.dropped_tasks})")
    check(len(reference) == 2 * SNAPSHOTS,
          f"{name}: nyx counted halos in both slabs of {SNAPSHOTS} snapshots")
    bad = {k: (v, reference.get(k)) for k, v in counts.items()
           if v != reference.get(k)}
    check(not bad, f"{name}: {len(counts)} delivered halo counts and "
                   f"checksums equal nyx's own (mismatches: {bad})")
    if every:
        check(set(counts) == set(reference),
              f"{name}: every snapshot reached both reeber instances")
    else:
        check(all((s, SNAPSHOTS - 1) in counts for s in range(2)),
              f"{name}: the last snapshot reached both reeber instances")
    print(f"  (halo count, checksum) {sorted(counts.items())}", flush=True)


def phase_workflow(seed: int) -> None:
    import jax

    for name, io_freq in (("all", 1), ("latest", -1)):
        t0 = time.monotonic()
        report, _, reference, counts, _ = run_workflow(
            seed, io_freq, jax.devices()[:1])
        print(f"  workflow io_freq={io_freq}: "
              f"{time.monotonic() - t0:.3f} s", flush=True)
        check_run(name, report, reference, counts, every=io_freq == 1)


def phase_four_chip(seed: int) -> None:
    import jax

    devices = jax.devices()
    check(len(devices) >= 4, f"four chips present ({len(devices)} found)")
    report, groups, reference, counts, placed = run_workflow(
        seed, 1, devices[:4])
    check_run("four-chip", report, reference, counts, every=True)
    ids = {k: [d.id for d in g] for k, g in groups.items()}
    print(f"  device groups {ids}", flush=True)
    flat = [d for g in ids.values() for d in g]
    check(len(flat) == len(set(flat)) == 4,
          "the four device groups are disjoint")
    check(len(ids[("nyx", 0)]) == 2, "nyx holds two chips")
    strays = [(t, i, what, sorted(d.id for d in devs))
              for t, i, what, devs in placed
              if not devs <= set(groups[(t, i)])]
    check(not strays and len(placed) > SNAPSHOTS,
          f"{len(placed)} task arrays sit on their own group only "
          f"(strays: {strays})")
    nyx_devs = {frozenset(d.id for d in devs) for t, _, what, devs in placed
                if what == "field"}
    check(nyx_devs == {frozenset(ids[("nyx", 0)])},
          f"nyx's field is sharded over both of its chips ({nyx_devs})")
    report1, _, _, counts1, _ = run_workflow(seed, 1, devices[:1])
    check(not report1.failures, "one-chip run: no task failed")
    check(counts == counts1,
          "halo counts and checksums equal the one-chip run's")


# ----------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip workflow and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU: JAX found {len(devices)} {platform!r} "
              f"device(s) ({devices[0].device_kind}); this check runs only "
              f"on a TPU chip", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from benchmarks.common import use_compile_cache
        import repro.core  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository's code is not beside this "
              f"script: {e}", file=sys.stderr)
        return 2
    cache = use_compile_cache()
    print(f"compile cache: {cache}: {cache_size(cache)} at start", flush=True)
    print(f"device: {platform} {devices[0].device_kind} x{len(devices)}",
          flush=True)

    compiles = Compiles()
    phases = ([("four_chip", phase_four_chip)] if args.four_chip else
              [("kernels", phase_kernels), ("workflow", phase_workflow)])
    failed = []
    for name, fn in phases:
        print(f"phase {name}", flush=True)
        n0, t0 = compiles.n, time.monotonic()
        try:
            fn(args.seed)
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'passed'} "
              f"in {time.monotonic() - t0:.3f} s, "
              f"{compiles.n - n0} compiles", flush=True)
    print(f"compile cache: {cache}: {cache_size(cache)} at end", flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
