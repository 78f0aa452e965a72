"""Faults planted under the timed path, to show that ``correct`` catches them.

Each is a context manager that patches the system under test for the length
of a run and puts it back after:

* ``control`` -- the transport keeps float32 payloads in bfloat16, the step
  that would tempt a change that halves the bytes a snapshot moves;
* ``stale``   -- a step that returns its state unchanged: every snapshot of
  a dataset after the first carries the previous one's values;
* ``half``    -- half of the batch left out: each consumer instance's slab
  holds the first half of its rows only;
* ``altered`` -- an answer altered where it is produced: one value of every
  slab served is changed in its lowest bit;
* ``dropped`` -- flow control loses a snapshot: each channel's fourth serve
  is skipped.

The benchmark's own runs plant none of them.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterator

import numpy as np


@contextlib.contextmanager
def _patched(owner, name: str, make: Callable) -> Iterator[None]:
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


def _bf16(data):
    import jax.numpy as jnp

    if getattr(data, "dtype", None) != np.float32:
        return data
    return jnp.asarray(data).astype(jnp.bfloat16).astype(jnp.float32)


def control():
    from repro.core.datamodel import Dataset

    def make(orig):
        def init(self, name, shape, dtype, data=None, parent=None, copy=True):
            if data is not None and copy:
                data = _bf16(data)
            orig(self, name, shape, dtype, data, parent, copy)
        return init
    return _patched(Dataset, "__init__", make)


def stale():
    from repro.core.datamodel import Dataset

    last: Dict[str, object] = {}

    def make(orig):
        def init(self, name, shape, dtype, data=None, parent=None, copy=True):
            if data is not None and copy:
                prev = last.get(name)
                last[name] = data
                if prev is not None and tuple(prev.shape) == tuple(data.shape):
                    data = prev
            orig(self, name, shape, dtype, data, parent, copy)
        return init
    return _patched(Dataset, "__init__", make)


def half():
    from repro.core.datamodel import Dataset

    def make(orig):
        def slab_view(self, starts, shape, parent=None):
            shape = (max(1, shape[0] // 2),) + tuple(shape[1:])
            return orig(self, starts, shape, parent)
        return slab_view
    return _patched(Dataset, "slab_view", make)


def altered():
    from repro.core.datamodel import Dataset

    def make(orig):
        def slab_view(self, starts, shape, parent=None):
            ds = orig(self, starts, shape, parent)
            data = np.array(ds._data)
            flat = data.reshape(-1).view(np.uint8)
            flat[0] ^= 1
            ds._data = data
            return ds
        return slab_view
    return _patched(Dataset, "slab_view", make)


def dropped():
    from repro.core.channel import Channel

    calls: Dict[int, int] = {}

    def make(orig):
        def offer(self, f, _payload_cache=None):
            calls[id(self)] = calls.get(id(self), 0) + 1
            if calls[id(self)] == 4:
                return False
            return orig(self, f, _payload_cache)
        return offer
    return _patched(Channel, "offer", make)


FAULTS = {"control": control, "stale": stale, "half": half,
          "altered": altered, "dropped": dropped}


def plant(name: str):
    """The fault ``name`` as a context manager; ``none`` plants nothing."""
    if name == "none":
        return contextlib.nullcontext()
    return FAULTS[name]()
