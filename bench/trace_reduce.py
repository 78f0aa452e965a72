"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
readings.

* busy time: the union of the op intervals on each device's op line,
  clipped to the measured window, averaged over the devices;
* the device ops that took the most time, summed by name and prefixed with
  the XLA module that ran them where the trace has a module line;
* the idle gaps of the device, each labelled by the harness annotations
  (``jax.profiler.TraceAnnotation`` names starting with ``bench/``) open on
  the host at the gap's midpoint, one per host thread, summed by label.

The window is the ``bench/traced`` annotation the producer holds open over
the profiled tail: from the first step after the measured window to the
step boundary ``TRACE_SECONDS`` later.  Everything here is plain interval
arithmetic on (start, end) pairs in seconds; ``reduce_trace`` only reads the
file.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

PREFIX = "bench/"
WINDOW = PREFIX + "traced"
TOP = 10


def union(ivs: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping or touching intervals; sorted, disjoint."""
    out: List[Interval] = []
    for a, b in sorted(ivs):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def total(ivs: Iterable[Interval]) -> float:
    return sum(b - a for a, b in ivs)


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` not covered by the merged ``busy``."""
    out: List[Interval] = []
    t = window[0]
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if window[1] > t:
        out.append((t, window[1]))
    return out


def label_at(t: float, annotations: Sequence[Tuple[str, str, float, float]]
             ) -> str:
    """What the host was doing at ``t``: per thread the innermost harness
    annotation open then (the latest to start), joined over threads."""
    inner: Dict[str, Tuple[float, str]] = {}
    for name, thread, a, b in annotations:
        if a <= t < b and (thread not in inner or a >= inner[thread][0]):
            inner[thread] = (a, name)
    names = sorted({n[len(PREFIX):] if n.startswith(PREFIX) else n
                    for _, n in inner.values()})
    return "+".join(names) if names else "(no annotation)"


def idle_by_label(idle: Sequence[Interval],
                  annotations: Sequence[Tuple[str, str, float, float]]
                  ) -> List[Tuple[str, float]]:
    """Idle seconds summed by the label at each gap's midpoint, largest
    first, at most ``TOP`` entries."""
    sums: Dict[str, float] = {}
    for a, b in idle:
        key = label_at((a + b) / 2, annotations)
        sums[key] = sums.get(key, 0.0) + (b - a)
    return sorted(sums.items(), key=lambda kv: -kv[1])[:TOP]


def short_name(name: str) -> str:
    """An op or module name without its HLO text or fingerprint: a TPU
    trace names an op ``%fusion.13 = f32[...] fusion(...)`` and a module
    ``jit_step(1185...)``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\(\d+\)$", "", name)


def top_ops(ops: Sequence[Tuple[str, float, float]],
            modules: Sequence[Tuple[str, float, float]] = ()
            ) -> List[Tuple[str, float]]:
    """Op seconds summed by ``module:op`` name, largest first, at most
    ``TOP`` entries.  An op takes the name of the module whose interval
    holds its start."""
    mods = sorted(modules, key=lambda m: m[1])
    starts = [m[1] for m in mods]
    sums: Dict[str, float] = {}
    for name, a, b in ops:
        name = short_name(name)
        k = bisect.bisect_right(starts, a) - 1
        if k >= 0 and mods[k][1] <= a < mods[k][2]:
            name = f"{short_name(mods[k][0])}:{name}"
        sums[name] = sums.get(name, 0.0) + (b - a)
    return sorted(sums.items(), key=lambda kv: -kv[1])[:TOP]


def reduce_events(devices: Dict[str, Dict[str, List[Tuple[str, float, float]]]],
                  annotations: Sequence[Tuple[str, str, float, float]]
                  ) -> Optional[Dict[str, object]]:
    """The readings from already-parsed events.

    ``devices`` maps a device name to ``{"ops": [(name, t0, t1)], "modules":
    [...]}``; ``annotations`` are host ``(name, thread, t0, t1)``.  Returns
    None when there is no window annotation or no device op in it."""
    win = [(a, b) for name, _, a, b in annotations if name == WINDOW]
    if not win:
        return None
    window = (min(a for a, _ in win), max(b for _, b in win))
    busy_per_device: Dict[str, float] = {}
    idle_all: List[Interval] = []
    ops_all: List[Tuple[str, float, float]] = []
    mods_all: List[Tuple[str, float, float]] = []
    for dev, ev in devices.items():
        ops = [(n, max(a, window[0]), min(b, window[1]))
               for n, a, b in ev.get("ops", [])
               if min(b, window[1]) > max(a, window[0])]
        busy = union((a, b) for _, a, b in ops)
        busy_per_device[dev] = total(busy)
        idle_all.extend(gaps(busy, window))
        ops_all.extend(ops)
        mods_all.extend(ev.get("modules", []))
    if not ops_all:
        return None
    n = len(busy_per_device)
    return {
        "window": window,
        "window_s": window[1] - window[0],
        "busy_s": sum(busy_per_device.values()) / n,
        "busy_per_device": busy_per_device,
        "device_ops": [[k, v] for k, v in top_ops(ops_all, mods_all)],
        "idle_gaps": [[k, v / n] for k, v in idle_by_label(idle_all, annotations)],
    }


# ------------------------------------------------------------ the file
def find_xplane(log_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def parse(path: str, device_lines: Sequence[Tuple[str, str]],
          module_line: str = "XLA Modules"
          ) -> Tuple[Dict[str, Dict[str, list]], List[Tuple[str, str, float, float]]]:
    """Read ``path`` into ``reduce_events``' inputs.

    ``device_lines`` lists ``(plane name, line name prefix)`` pairs whose
    events are device ops (``("/device:TPU:0", "XLA Ops")`` on a TPU).
    Host annotations are the events named ``bench/...`` on ``/host:`` planes.
    """
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    annotations: List[Tuple[str, str, float, float]] = []
    wanted: Dict[str, List[str]] = {}
    for plane_name, prefix in device_lines:
        wanted.setdefault(plane_name, []).append(prefix)
    # a device whose plane holds no op reads as idle the whole window
    devices = {p: {"ops": [], "modules": []} for p in wanted}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for k, line in enumerate(plane.lines):
                thread = f"{plane.name}#{k}:{line.name}"
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        annotations.append((e.name, thread, e.start_ns * 1e-9,
                                            (e.start_ns + e.duration_ns) * 1e-9))
        if plane.name not in wanted:
            continue
        ev = devices[plane.name]
        for line in plane.lines:
            if any(line.name.startswith(p) for p in wanted[plane.name]):
                key = "ops"
            elif line.name == module_line:
                key = "modules"
            else:
                continue
            ev[key].extend((e.name, e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events if e.duration_ns > 0)
    return devices, annotations


def reduce_trace(log_dir: str, device_lines: Sequence[Tuple[str, str]]
                 ) -> Optional[Dict[str, object]]:
    path = find_xplane(log_dir)
    if path is None:
        return None
    return reduce_events(*parse(path, device_lines))


def tpu_lines(device_ids: Iterable[int]) -> List[Tuple[str, str]]:
    return [(f"/device:TPU:{i}", "XLA Ops") for i in device_ids]
