"""In-program spans at the layer boundaries of the served path.

``span(name, key)`` is a context manager placed where the engine, the
runner and the controller hand work to one another. Off (the default) it
returns one shared no-op object after a single flag check: no clock
read, no allocation. On (``enable()``) it records the span's name, its
parent (the innermost recorded span open when it started), its key (the
request on request-scoped spans) and its start and end on
``time.perf_counter_ns``, in preallocated arrays; it also enters
``jax.profiler.TraceAnnotation(name)``, so a profiler trace shows the
span on its host line beside the device's operations, on the same clock.

``summary(t0, t1)`` reduces the recorded spans that lie wholly inside
``[t0, t1]`` (``time.perf_counter`` seconds) to a count, total, self time
(the duration less the child spans it holds) and maximum per name.

    from repro import tracing
    tracing.enable()
    ...  # serve
    tracing.summary(t_open, t_close)["runner.wait"]["total_s"]

Spans nest by ``with`` order on one thread; the recorder is not meant for
spans opened concurrently from several threads.
"""
from __future__ import annotations

import time
from typing import Dict, Optional

import numpy as np
from jax.profiler import TraceAnnotation

#: Every span name the program records, in the order of the layers.
NAMES = (
    "engine.admit",  # GenerativeAdapter._admit_one (key: rid)
    "engine.window",  # GenerativeAdapter._step: one sync window
    "engine.replay",  # the per-step replay of a window's records
    "runner.prefill",  # DecodeRunner.start: the prefill and its first token (key: item)
    "runner.prepare",  # step_multi: validation, block claims, argument transfers
    "runner.dispatch",  # step_multi: the jitted window's call
    "runner.wait",  # step_multi: the one sync (executed-step count)
    "runner.drain",  # step_multi: record copies, slicing, tail release
    "controller.decide",  # observe: record window append and exit decisions
    "controller.monitor",  # observe: windowed accuracy (evaluate_config)
    "controller.tune",  # _tune: threshold tuning
    "controller.adjust",  # _adjust: ramp adjustment
)
_ID = {n: i for i, n in enumerate(NAMES)}

#: Spans held before further ones are counted as dropped.
CAPACITY = 1 << 18

_on = False


class _Off:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


class _Recorder:
    def __init__(self, capacity: int):
        self.name = np.zeros(capacity, np.int16)
        self.parent = np.full(capacity, -1, np.int64)
        self.key = np.full(capacity, -1, np.int64)
        self.t0 = np.zeros(capacity, np.int64)
        self.t1 = np.zeros(capacity, np.int64)
        self.n = 0
        self.dropped = 0
        self.open = -1  # index of the innermost recorded span still open

    def reset(self):
        self.n = 0
        self.dropped = 0
        self.open = -1


_rec: Optional[_Recorder] = None


class _Span:
    __slots__ = ("rec", "i", "prev", "ann")

    def __init__(self, rec: _Recorder, name: str, key: int):
        self.rec = rec
        self.prev = rec.open
        i = rec.n
        if i >= len(rec.name):
            rec.dropped += 1
            self.i = -1
        else:
            rec.n = i + 1
            rec.name[i] = _ID[name]
            rec.parent[i] = self.prev
            rec.key[i] = key
            rec.open = self.i = i
        self.ann = TraceAnnotation(name)

    def __enter__(self):
        self.ann.__enter__()
        if self.i >= 0:
            self.rec.t0[self.i] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        if self.i >= 0:
            self.rec.t1[self.i] = time.perf_counter_ns()
            self.rec.open = self.prev
        self.ann.__exit__(*exc)
        return False


def span(name: str, key: int = -1):
    """A span named ``name`` (one of ``NAMES``); ``key`` identifies the
    request on request-scoped spans."""
    if not _on:
        return _OFF
    return _Span(_rec, name, int(key))


def enable(capacity: int = CAPACITY) -> None:
    """Record spans from now on, keeping those already recorded."""
    global _on, _rec
    if _rec is None or len(_rec.name) != capacity:
        _rec = _Recorder(capacity)
    _on = True


def disable() -> None:
    """Stop recording; what was recorded stays readable."""
    global _on
    _on = False


def reset() -> None:
    """Forget every recorded span and the count of dropped ones."""
    if _rec is not None:
        _rec.reset()


def dropped() -> int:
    """Spans not recorded because the arrays were full."""
    return 0 if _rec is None else _rec.dropped


def spans() -> Dict[str, np.ndarray]:
    """The recorded spans, as arrays: ``name`` (index into ``NAMES``),
    ``parent`` (row of the enclosing span, -1 where none), ``key``,
    ``t0_ns`` and ``t1_ns``. A span still open has ``t1_ns`` 0."""
    n = 0 if _rec is None else _rec.n
    if not n:
        z = np.zeros(0, np.int64)
        return {"name": z, "parent": z, "key": z, "t0_ns": z, "t1_ns": z}
    return {"name": _rec.name[:n].astype(np.int64), "parent": _rec.parent[:n].copy(),
            "key": _rec.key[:n].copy(), "t0_ns": _rec.t0[:n].copy(),
            "t1_ns": _rec.t1[:n].copy()}


def summary(t0: float, t1: float) -> Dict[str, dict]:
    """Per name, over the closed spans wholly inside ``[t0, t1]``
    (``time.perf_counter`` seconds): ``count``, ``total_s``, ``self_s``
    (each span's duration less its recorded children's) and ``max_s``.
    Names with no such span are left out."""
    s = spans()
    dur = s["t1_ns"] - s["t0_ns"]
    closed = s["t1_ns"] > 0
    child = np.zeros(len(dur), np.int64)
    has_parent = closed & (s["parent"] >= 0)
    np.add.at(child, s["parent"][has_parent], dur[has_parent])
    inside = closed & (s["t0_ns"] >= int(t0 * 1e9)) & (s["t1_ns"] <= int(t1 * 1e9))
    out = {}
    for i, name in enumerate(NAMES):
        m = inside & (s["name"] == i)
        if m.any():
            d = dur[m]
            out[name] = {"count": int(m.sum()), "total_s": float(d.sum()) * 1e-9,
                         "self_s": float((d - child[m]).sum()) * 1e-9,
                         "max_s": float(d.max()) * 1e-9}
    return out
