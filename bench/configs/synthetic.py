"""The paper's synthetic M->N benchmark (Wilkins, arXiv:2404.03591, §4):
each producer process writes a grid of 64-bit points and a set of particles
(3 x f32) every step; consumer instances with declared ownership receive
their block of both.

The producer task stands for ``producer_nprocs`` processes: it writes the
global datasets, and its outport's ``ownership: {axis: 0}`` gives each
process its even block.  The grid's 64-bit points are held as pairs of
uint32, the same bytes, so that no 64-bit mode is needed on the device.
Each step's data is made on the device from the seed and the step.  Each
consumer instance puts its block of both datasets on its device and takes
a position-weighted checksum of each.

``reference`` makes each step's data again from the seed, cuts each
instance's rows by plain arithmetic and checksums them; it goes through no
part of ``repro``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Tuple

PORT = "out*.h5"
GRID = "/group1/grid"
PARTICLES = "/group1/particles"
WORKFLOW = """
tasks:
  - func: producer
    nprocs: {producer_nprocs}
    outports:
      - filename: out*.h5
        ownership: {{axis: 0}}
        dsets: [{{name: /group1/grid, memory: 1}}, {{name: /group1/particles, memory: 1}}]
  - func: consumer
    nprocs: {consumer_nprocs}
    taskCount: {consumer_instances}
    inports:
      - filename: out*.h5
        io_freq: {io_freq}
        redistribute: 1
        dsets: [{{name: /group1/grid, memory: 1}}, {{name: /group1/particles, memory: 1}}]
"""


@functools.cache
def _jits(points: int):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key, step):
        """Step ``step``'s grid (``points`` x 2 uint32) and particles
        (``points`` x 3 f32)."""
        k = jax.random.fold_in(key, step)
        grid = jax.random.bits(jax.random.fold_in(k, 0), (points, 2),
                               jnp.uint32)
        parts = jax.random.uniform(jax.random.fold_in(k, 1), (points, 3),
                                   jnp.float32)
        return grid, parts

    def checksum(a):
        bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
        pos = jnp.arange(a.size, dtype=jnp.uint32).reshape(a.shape)
        return jnp.sum(bits * (pos * jnp.uint32(2654435761) + 1),
                       dtype=jnp.uint32)

    @jax.jit
    def analyse(grid, parts):
        """A uint32 sum of each value's bits weighted by its flat position,
        for each dataset: exact in any order, and a moved row or a changed
        value shows."""
        return checksum(grid), checksum(parts)

    return make, analyse


def rows(n: int, parts: int, i: int) -> Tuple[int, int]:
    """Instance ``i``'s even block of ``n`` rows: (start, count)."""
    base, rem = divmod(n, parts)
    return i * base + min(i, rem), base + (1 if i < rem else 0)


def workflow(cfg: Dict[str, Any], io_freq: int) -> str:
    return WORKFLOW.format(io_freq=io_freq, **cfg)


def _points(cfg: Dict[str, Any]) -> int:
    return int(cfg["points_per_process"]) * int(cfg["producer_nprocs"])


def tasks(run) -> Dict[str, Any]:
    import jax

    from repro.core import h5

    make, analyse = _jits(_points(run.cfg))

    def producer(comm):
        key = run.key()
        t = 0
        while run.keep_going(t):
            with run.span("step", "producer", 0, t):
                grid, parts = make(key, t)
                parts.block_until_ready()
            with run.span("write", "producer", 0, t), \
                    h5.File(f"out{t:06d}.h5", "w") as f:
                f.create_dataset(GRID, data=grid).attrs["step"] = t
                f.create_dataset(PARTICLES, data=parts)
                run.closing(t)
            run.closed(t)
            t += 1

    def consumer(comm):
        device = comm.mesh().devices.flat[0]
        while True:
            with run.span("open", "consumer", comm.instance):
                f = h5.File(PORT, "r")
            if f is None:
                return
            g, p = f[GRID], f[PARTICLES]
            step = int(g.attrs["step"])
            with run.span("h2d", "consumer", comm.instance, step):
                grid, parts = jax.device_put((g[:], p[:]), device)
                parts.block_until_ready()
                grid.block_until_ready()
            with run.span("analyse", "consumer", comm.instance, step):
                result = [int(v) for v in analyse(grid, parts)]
            box = (g.attrs["redist_box_starts"], g.shape)
            if (p.attrs["redist_box_starts"][0], p.shape[0]) != (
                    box[0][0], box[1][0]):
                box = None  # the two datasets' rows disagree
            run.deliver(comm.instance, step, result, box)

    return {"producer": producer, "consumer": consumer}


def reference(run, wanted: List[Tuple[int, int]]) -> Dict[Tuple[int, int], Any]:
    """Each (instance, step)'s grid box and checksums, from the seed alone."""
    import jax

    n_pts = _points(run.cfg)
    n = run.consumers
    make, analyse = _jits(n_pts)
    key = run.key()
    out: Dict[Tuple[int, int], Any] = {}
    for i, t in wanted:
        if not 0 <= i < n:
            out[(i, t)] = {"box": None, "result": None}
            continue
        grid, parts = make(key, t)
        start, count = rows(n_pts, n, i)
        out[(i, t)] = {
            "box": [[start, 0], [count, 2]],
            "result": [int(v) for v in analyse(
                jax.lax.slice_in_dim(grid, start, start + count),
                jax.lax.slice_in_dim(parts, start, start + count))]}
    return out
