"""Reduction of a profiler trace to device numbers.

Reads the ``.xplane.pb`` the JAX profiler writes, with nothing but JAX
(``jax.profiler.ProfileData``). On a TPU each chip is a ``/device:TPU:<n>``
plane: its ``XLA Modules`` line holds one event per program execution
(``jit_decm(<hash>)``), its ``XLA Ops`` line one event per operation, an
operation nested inside another (a ``while`` loop's body ops inside the
loop) sitting inside its parent's interval. Host spans are the
``TraceAnnotation`` events the harness writes around the engine's calls
(``runner.start``, ``runner.step_multi``, ``controller.observe``).

Everything is computed over the traced interval, from the first host
span's start to the last one's end, and averaged over the chips:
  * busy: the union of the operations' intervals;
  * an operation's time: its self time (its interval less its children's),
    keyed by its HLO name (``%attend_decode_paged.9``), with its full HLO
    text kept so a reader can tell kernels apart by signature;
  * a program's time: its executions on the ``XLA Modules`` line;
  * collectives: the union of all-gather, all-reduce and similar ops;
  * idle gaps: the intervals with no operation running, attributed to the
    host span they fall in (``engine (between calls)`` where none).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

SPANS = ("runner.start", "runner.step_multi", "controller.observe")
COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "collective-permute",
               "all-to-all")
BETWEEN = "engine (between calls)"


def profile_options():
    """The profiler without its Python tracer (which slows every call)."""
    import jax

    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    return o


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    return paths[-1] if paths else None


def short(name: str) -> str:
    """``%fusion.12`` of ``%fusion.12 = bf16[...] fusion(...)``."""
    return name.split(" = ", 1)[0]


def load(path: str):
    """Per device: its ops (full name, start_ns, end_ns) and its program
    executions (name, start_ns, end_ns); and the host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops: Dict[str, list] = {}
    mods: Dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops[plane.name], mods[plane.name] = [], []
            for line in plane.lines:
                dst = {"XLA Ops": ops, "XLA Modules": mods}.get(line.name)
                if dst is not None:
                    dst[plane.name].extend(
                        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                             for ev in line.events if ev.name in SPANS)
    return ops, mods, sorted(spans, key=lambda s: s[1])


def union(intervals) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def self_times(events) -> Dict[str, float]:
    """Self time (ns) of each op, keyed by full name: an op's interval less
    the intervals of the ops nested in it."""
    evs = sorted(events, key=lambda x: (x[1], -x[2]))
    out: Dict[str, float] = defaultdict(float)
    stack: list = []  # [name, start, end, child_ns]
    for name, s, e in evs:
        while stack and s >= stack[-1][2]:
            n, s0, e0, ch = stack.pop()
            out[n] += (e0 - s0) - ch
        if stack:
            stack[-1][3] += e - s
        stack.append([name, s, e, 0])
    while stack:
        n, s0, e0, ch = stack.pop()
        out[n] += (e0 - s0) - ch
    return out


def is_collective(name: str) -> bool:
    n = short(name).lower()
    return any(c in n for c in COLLECTIVES)


def reduce(ops: Dict[str, list], mods: Dict[str, list], spans: list, *,
           top: int = 10) -> Optional[dict]:
    if not ops or not spans:
        return None
    lo, hi = spans[0][1], max(s[2] for s in spans)
    nd = len(ops)
    busy = coll = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    full: Dict[str, str] = {}
    mod_s: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    starts = [s[1] for s in spans]
    for dev, evs in ops.items():
        evs = [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]
        u = union((s, e) for _, s, e in evs)
        busy += sum(e - s for s, e in u)
        coll += sum(e - s for s, e in union((s, e) for n, s, e in evs if is_collective(n)))
        for n, t in self_times(evs).items():
            op_s[short(n)] += t
            full.setdefault(short(n), n)
        prev = lo
        for s, e in u + [(hi, hi)]:
            if s > prev:
                attribute(prev, s, spans, starts, gaps)
            prev = max(prev, e)
        for n, s, e in mods.get(dev, []):
            if e > lo and s < hi:
                mod_s[n.split("(", 1)[0]] += min(e, hi) - max(s, lo)
    per = 1e-9 / nd
    op_s = {k: v * per for k, v in op_s.items()}
    return {
        "devices": nd,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * per,
        "collective_s": coll * per,
        "op_s": op_s,
        "op_full": full,
        "program_s": {k: v * per for k, v in mod_s.items()},
        "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(((k, v * per) for k, v in gaps.items()), key=lambda kv: -kv[1])[:top],
    }


def attribute(s, e, spans, starts, acc):
    """Split the idle interval [s, e) among the host spans it overlaps."""
    t = s
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    while i < len(spans) and t < e:
        name, a, b = spans[i]
        i += 1
        if b <= t:
            continue
        if a >= e:
            break
        if a > t:
            acc[BETWEEN] += a - t
            t = a
        stop = min(b, e)
        if stop > t:
            acc[name] += stop - t
            t = stop
    if e > t:
        acc[BETWEEN] += e - t


def op_time(red: dict, match: Callable[[str, str], bool]) -> float:
    """Device seconds (per chip) of the ops ``match(short, full)`` accepts."""
    return sum(v for k, v in red["op_s"].items() if match(k, red["op_full"][k]))


_LAYOUT = r"(?:\{[^}]*\})?"
_EXIT_HEAD = re.compile(
    r"= \((?:f32\[\d+\]" + _LAYOUT + r", ){3}s32\[\d+\]" + _LAYOUT + r", s32\[\d+\]"
    + _LAYOUT + r"\) custom-call\(")


def is_paged_attention(short_name: str, full_name: str) -> bool:
    return short_name.startswith("%attend_decode_paged")


def is_exit_head(short_name: str, full_name: str) -> bool:
    """The fused ramp-head exit kernel: a Mosaic call returning (max, sum,
    weighted sum, label, exit) per row."""
    return 'custom_call_target="tpu_custom_call"' in full_name and bool(_EXIT_HEAD.search(full_name))
