"""Nyx + Reeber (Wilkins paper, arXiv:2404.03591, cosmology use case,
Table 3): a density field evolved on the device and written through
``h5.File`` every step; ``reeber`` instances each receive an axis-0 slab,
put it on their device and count halos.

The task functions are the deployment's own code: plain ``h5`` calls, no
workflow calls, each call wrapped in ``run.span`` (a harness span and a
``jax.profiler.TraceAnnotation``).  ``comm`` is used only for the task's
devices.  Sizes come from the configuration's JSON file:

* ``shape``              -- the global field, f32, sharded over nyx's
  devices along axis 0;
* ``consumer_instances`` -- reeber's ``taskCount``; instance ``i`` owns the
  ``i``-th even block of rows.

``reference`` recomputes each snapshot from the seed with the same
generator, cuts each instance's rows by plain arithmetic and analyses them
itself; it goes through no part of ``repro``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

PORT = "plt*.h5"
DSET = "/level_0/density"
CUTOFF = 1.5
WORKFLOW = """
tasks:
  - func: nyx
    nprocs: {nyx_nprocs}
    outports:
      - filename: plt*.h5
        dsets: [{{name: /level_0/density, memory: 1}}]
  - func: reeber
    nprocs: {reeber_nprocs}
    taskCount: {consumer_instances}
    inports:
      - filename: plt*.h5
        io_freq: {io_freq}
        redistribute: {{axis: 0}}
        dsets: [{{name: /level_0/density, memory: 1}}]
"""


@functools.cache
def _jits():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def nyx_step(rho, key):
        """Stand-in density evolution: diffusion plus multiplicative
        forcing, a few HBM passes over the field."""
        lap = (jnp.roll(rho, 1, 0) + jnp.roll(rho, -1, 0) +
               jnp.roll(rho, 1, 1) + jnp.roll(rho, -1, 1) +
               jnp.roll(rho, 1, 2) + jnp.roll(rho, -1, 2) - 6 * rho)
        force = jax.random.normal(key, rho.shape) * 0.02
        return jnp.clip(rho + 0.1 * lap + force * rho, 0.0, None)

    @jax.jit
    def analyse(slab):
        """(halo count, checksum): cells above the cutoff, and a uint32 sum
        of each value's bits weighted by its flat position, so a moved row
        or a changed value shows and the sum is exact in any order."""
        bits = jax.lax.bitcast_convert_type(slab, jnp.uint32)
        pos = jnp.arange(slab.size, dtype=jnp.uint32).reshape(slab.shape)
        return (jnp.sum(slab > CUTOFF, dtype=jnp.int32),
                jnp.sum(bits * (pos * jnp.uint32(2654435761) + 1),
                        dtype=jnp.uint32))

    return nyx_step, analyse


def initial(key, shape, sharding=None):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: jnp.exp(0.5 * jax.random.normal(
        k, shape, jnp.float32)), out_shardings=sharding)(key)


def rows(n: int, parts: int, i: int) -> Tuple[int, int]:
    """Instance ``i``'s even block of ``n`` rows: (start, count)."""
    base, rem = divmod(n, parts)
    return i * base + min(i, rem), base + (1 if i < rem else 0)


def workflow(cfg: Dict[str, Any], io_freq: int) -> str:
    return WORKFLOW.format(io_freq=io_freq, **cfg)


def tasks(run) -> Dict[str, Any]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import h5

    shape = tuple(run.cfg["shape"])
    nyx_step, analyse = _jits()

    def nyx(comm):
        mesh = comm.mesh()
        key = run.key()
        rho = initial(key, shape, NamedSharding(mesh, P(mesh.axis_names[0])))
        t = 0
        while run.keep_going(t):
            with run.span("step", "nyx", 0, t):
                rho = nyx_step(rho, jax.random.fold_in(key, t))
                rho.block_until_ready()
            with run.span("write", "nyx", 0, t), \
                    h5.File(f"plt{t:05d}.h5", "w") as f:
                f.create_dataset(DSET, data=rho).attrs["step"] = t
                run.closing(t)
            run.closed(t)
            t += 1

    def reeber(comm):
        device = comm.mesh().devices.flat[0]
        while True:
            with run.span("open", "reeber", comm.instance):
                f = h5.File(PORT, "r")
            if f is None:
                return
            ds = f[DSET]
            step = int(ds.attrs["step"])
            with run.span("h2d", "reeber", comm.instance, step):
                slab = jax.device_put(ds[:], device).block_until_ready()
            with run.span("analyse", "reeber", comm.instance, step):
                result = [int(v) for v in analyse(slab)]
            box = (ds.attrs["redist_box_starts"], ds.shape)
            run.deliver(comm.instance, step, result, box)

    return {"nyx": nyx, "reeber": reeber}


def reference(run, wanted: List[Tuple[int, int]]) -> Dict[Tuple[int, int], Any]:
    """Each (instance, step)'s slab box and analysis, from the seed alone.

    The field is regenerated step by step on one device; each wanted
    snapshot is cut into the instance's rows by ``rows`` and analysed."""
    import jax

    shape = tuple(run.cfg["shape"])
    n = run.consumers
    nyx_step, analyse = _jits()
    key = run.key()
    rho = initial(key, shape, jax.sharding.SingleDeviceSharding(
        jax.devices()[0]))
    by_step: Dict[int, List[int]] = {}
    for i, s in wanted:
        by_step.setdefault(s, []).append(i)
    out: Dict[Tuple[int, int], Any] = {}
    last = max(by_step, default=-1)
    for t in range(last + 1):
        rho = nyx_step(rho, jax.random.fold_in(key, t))
        for i in sorted(by_step.get(t, [])):
            if not 0 <= i < n:
                out[(i, t)] = {"box": None, "result": None}
                continue
            start, count = rows(shape[0], n, i)
            slab = jax.lax.slice_in_dim(rho, start, start + count, axis=0)
            out[(i, t)] = {
                "box": [[start] + [0] * (len(shape) - 1),
                        [count] + list(shape[1:])],
                "result": [int(v) for v in analyse(slab)]}
    return out
