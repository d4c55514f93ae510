"""The served path, instrumented from outside.

``build`` wires the program's own serving stack exactly as
``repro.launch.serve.build_generative_engine`` does: a paged
``DecodeRunner`` (``ShardedDecodeRunner`` on a tp mesh), a live
``ApparateController`` and a ``GenerativeEngine``. ``Instrumented``
wraps the three calls the engine makes into them (``runner.start``,
``runner.step_multi``, ``controller.observe``, and ``runner.free``) with
host timestamps written into preallocated arrays, and, in traced runs,
with ``jax.profiler.TraceAnnotation`` spans. No scheduling is added: the
engine decides every admission and window.

Two things the wrappers do besides timing:
  * The runner keeps one prompt array of one length; before each
    ``start`` the wrapper points it at a view of the backlog cut to that
    request's prompt length, so each length runs as its own prefill
    program (the runner compiles one per length).
  * Once the measured window has closed, the next call raises
    ``WindowClosed``, which ends ``GenerativeEngine.run`` where it stands
    instead of draining the backlog.
"""
from __future__ import annotations

import contextlib
import time
from typing import List, Optional

import numpy as np

START, WINDOW, OBSERVE, FREE = 0, 1, 2, 3


class WindowClosed(Exception):
    """Raised by the first runner call after the window's end."""


class Compiles:
    """Programs lowered and compiled, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self.names: List[str] = []  # programs lowered, in order
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
            self.names.append(str(kw.get("fun_name", "?")))
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def count(self) -> int:
        return self.lowered + self.compiled


class GcPauses:
    """Host time the Python garbage collector held the process, from
    ``gc.callbacks``: (start, end, generation) of every collection."""

    def __init__(self):
        import gc

        self.spans: List[tuple] = []
        self._t0 = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.spans.append((self._t0, time.perf_counter(), int(info["generation"])))
            self._t0 = None

    def close(self):
        import gc

        gc.callbacks.remove(self._cb)


class Timeline:
    """Preallocated event arrays: kind, host start/end, two integers and a
    payload reference (a window's records)."""

    def __init__(self, cap: int = 1 << 18):
        self.kind = np.zeros(cap, np.int8)
        self.t0 = np.zeros(cap)
        self.t1 = np.zeros(cap)
        self.a = np.zeros(cap, np.int64)
        self.b = np.zeros(cap, np.int64)
        self.payload: List = [None] * cap
        self.n = 0

    def add(self, kind, t0, t1, a=0, b=0, payload=None):
        i = self.n
        if i >= len(self.kind):
            raise RuntimeError("timeline full")
        self.kind[i], self.t0[i], self.t1[i], self.a[i], self.b[i] = kind, t0, t1, a, b
        self.payload[i] = payload
        self.n = i + 1

    def view(self, kind):
        m = self.kind[: self.n] == kind
        idx = np.nonzero(m)[0]
        return idx


def program_config(conf: dict):
    """The program's model config for this benchmark configuration."""
    from repro.configs import get_config

    prog = conf["program"]
    return get_config(prog["registry"]).replace(**prog["overrides"])


def build(conf: dict, params, *, prompt_width: int, max_new: int, mesh=None):
    """The served engine over ``params``, as the program wires it, with
    slot caches for prompts of up to ``prompt_width`` tokens and outputs
    of up to ``max_new``."""
    from repro.core import build_profile
    from repro.launch.serve import build_generative_engine
    from repro.models import build_model
    from repro.serving import GenerativeConfig

    cfg = program_config(conf)
    model = build_model(cfg)
    s = conf["serving"]
    prof = build_profile(cfg, mode="decode", chips=int(s.get("tp", 1)), charge_kv=True)
    return build_generative_engine(
        model, params, np.zeros((1, prompt_width), np.int32), prof,
        GenerativeConfig(max_batch_size=s["slots"], steps_per_sync=s["steps_per_sync"]),
        controller_config(conf), max_new_tokens=max_new,
        kv_block_size=s["block_size"], mesh=mesh)


def controller_config(conf: dict):
    from repro.core import ControllerConfig

    c = conf["serving"]["controller"]
    return ControllerConfig(max_slots=conf["serving"]["ramp_slots"],
                            ramp_budget_frac=c["ramp_budget_frac"],
                            acc_constraint=c["acc_constraint"])


def fresh_controller(conf: dict, eng):
    from repro.core import ApparateController

    return ApparateController(len(eng.runner.model.sites), eng.profile, controller_config(conf))


class Instrumented:
    """Timestamps around the engine's calls into runner and controller."""

    def __init__(self, eng, timeline: Timeline, *, traced: bool = False):
        self.eng, self.tl, self.traced = eng, timeline, traced
        self.runner = eng.runner
        self._start = type(self.runner).start.__get__(self.runner)
        self._step = type(self.runner).step_multi.__get__(self.runner)
        self._free = type(self.runner).free.__get__(self.runner)
        self.runner.start = self.start
        self.runner.step_multi = self.step_multi
        self.runner.free = self.free
        self.backlog = None
        self.t_open: Optional[float] = None
        self.t_close: Optional[float] = None
        self.seconds = 0.0
        self.settle = 0  # completions before the window may open
        self.completed = 0
        self.on_open = None  # callback at the window's opening
        self.on_call = None  # callback at every runner call (the trace's start and stop)
        self.gate = lambda: True  # the window may open only once this holds
        self.adapter = None
        make = eng._make_adapter

        def capture(requests):
            self.adapter = make(requests)
            return self.adapter

        eng._make_adapter = capture
        self.observe_controller(eng.controller)

    def observe_controller(self, ctl):
        self.eng.controller = ctl
        orig = type(ctl).observe.__get__(ctl)

        def observe(*a, **kw):
            t0 = time.perf_counter()
            with self._span("controller.observe"):
                out = orig(*a, **kw)
            self.tl.add(OBSERVE, t0, time.perf_counter())
            return out

        ctl.observe = observe

    def _span(self, name):
        if not self.traced:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def _guard(self):
        if self.on_call is not None:
            self.on_call()
        if self.t_close is not None and time.perf_counter() >= self.t_close:
            raise WindowClosed

    def arm(self, backlog, seconds: float, settle: int):
        """Serve ``backlog``; the window opens at the first sync window
        dispatched after ``settle`` completions and lasts ``seconds``."""
        self.backlog, self.seconds, self.settle = backlog, seconds, settle
        self.t_open = self.t_close = None
        self.completed = 0

    def start(self, slot, item):
        self._guard()
        bl = self.backlog
        self.runner.prompts = bl.prompts[:, : int(bl.prompt_len[item])]
        t0 = time.perf_counter()
        with self._span("runner.start"):
            tok = self._start(slot, item)
        self.tl.add(START, t0, time.perf_counter(), slot, item)
        return tok

    def step_multi(self, slots, active, n_steps, thresholds):
        self._guard()
        t0 = time.perf_counter()
        if (self.t_open is None and self.seconds and self.completed >= self.settle
                and self.gate()):
            self.t_open, self.t_close = t0, t0 + self.seconds
            if self.on_open is not None:
                self.on_open()
                t0 = time.perf_counter()
        with self._span("runner.step_multi"):
            out = self._step(slots, active, n_steps, thresholds)
        labels, _, finals, exits = out
        self.tl.add(WINDOW, t0, time.perf_counter(), finals.shape[0], int(n_steps),
                    (tuple(slots), tuple(active), labels, finals, exits))
        return out

    def free(self, slot):
        t0 = time.perf_counter()
        self._free(slot)
        self.completed += 1
        self.tl.add(FREE, t0, time.perf_counter(), slot)


def serve(inst: Instrumented, backlog, *, seconds=0.0, settle=0, n=None):
    """Run the engine over the first ``n`` requests of ``backlog`` (all
    arriving at t=0). With ``seconds`` the run stops when the window has
    closed; without, it serves every request. True where the window
    closed."""
    from repro.serving.request import GenRequest

    n = len(backlog) if n is None else n
    reqs = [GenRequest(rid=i, arrival_ms=0.0, slo_ms=float("inf"), item=i,
                       prompt_len=int(backlog.prompt_len[i]),
                       n_tokens=int(backlog.n_tokens[i])) for i in range(n)]
    inst.arm(backlog, seconds, settle)
    try:
        inst.eng.run(reqs)
    except WindowClosed:
        return True
    return False


def release_live_slots(inst: Instrumented):
    """Free the slots an interrupted run left live."""
    if inst.adapter is None:
        return
    for sid in sorted(inst.adapter.slots):
        type(inst.runner).free(inst.runner, sid)
