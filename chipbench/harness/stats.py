"""Arithmetic on the host timeline: what each request received and when.

A request's first token reaches the host when ``runner.start`` returns;
the rest arrive in lumps, when the ``runner.step_multi`` call of each
sync window it took part in returns (every row of a window receives the
window's executed step count). Nothing here reads the engine's modeled
clock.
"""
from __future__ import annotations

import numpy as np

from harness.served import FREE, OBSERVE, START, WINDOW


def requests(tl, n_tokens):
    """Per request (indexed like the backlog): the host time of its first
    token, of its last token so far, of its completion (nan until done),
    and its token count; plus the windows' per-row bookkeeping."""
    N = len(n_tokens)
    first, last, end = (np.full(N, np.nan) for _ in range(3))
    count = np.zeros(N, np.int64)
    cur = {}
    before = {}  # window event index -> tokens each row had before it
    for i in range(tl.n):
        k = tl.kind[i]
        if k == START:
            slot, item = int(tl.a[i]), int(tl.b[i])
            cur[slot] = item
            count[item] = 1
            first[item] = last[item] = tl.t1[i]
            if n_tokens[item] == 1:
                end[item] = tl.t1[i]
        elif k == WINDOW:
            slots, nd, t = tl.payload[i][0], int(tl.a[i]), tl.t1[i]
            items = [cur[s] for s in slots]
            before[i] = (items, count[items].copy())
            for it in items:
                count[it] += nd
                last[it] = t
                if count[it] >= n_tokens[it]:
                    end[it] = t
    return dict(first=first, last=last, end=end, count=count, before=before)


def tokens_between(tl, t0, t1):
    """Output tokens the host received in [t0, t1]."""
    n = 0
    for i in range(tl.n):
        if not t0 <= tl.t1[i] <= t1:
            continue
        if tl.kind[i] == START:
            n += 1
        elif tl.kind[i] == WINDOW:
            n += len(tl.payload[i][0]) * int(tl.a[i])
    return n


def tpot_ms(req, n_tokens, t0, t1):
    """Per request finishing in [t0, t1] with two tokens or more: (last −
    first) / (tokens − 1), in ms."""
    end = req["end"]
    done = np.nonzero((end >= t0) & (end <= t1) & (np.asarray(n_tokens) > 1))[0]
    return done, 1e3 * (end[done] - req["first"][done]) / (np.asarray(n_tokens)[done] - 1)


def percentile(x, q):
    """The q-th percentile (linear between order statistics) and how many
    samples lie beyond it."""
    x = np.sort(np.asarray(x, np.float64))
    if not len(x):
        return None, 0
    v = float(np.percentile(x, q))
    return v, int((x > v).sum())


def host_record(tl, req, prompt_len, t0, t1, slots, gather_slots):
    """Sums over the calls that lie wholly inside [t0, t1]. A window with
    any active ramp runs ``gather_slots`` ramp heads per step."""
    inside = (tl.t0[: tl.n] >= t0) & (tl.t1[: tl.n] <= t1)
    idx = np.nonzero(inside)[0]
    dur = tl.t1[idx] - tl.t0[idx]
    kinds = tl.kind[idx]
    r = dict(span_s=float(tl.t1[idx].max() - t0) if len(idx) else 0.0,
             slots=int(slots), windows=0, steps=0, row_steps=0, ctx_row_steps=0,
             tokens=0, exits=0, ramp_calls=0, active_ramps=0, empty_active=0, agree={},
             window_s=float(dur[kinds == WINDOW].sum()),
             prefill_s=float(dur[kinds == START].sum()),
             controller_s=float(dur[kinds == OBSERVE].sum()),
             calls_s=float(dur.sum()))
    for i in idx[kinds == WINDOW]:
        slots_i, act, labels, finals, exits = tl.payload[i]
        nd, B = int(tl.a[i]), len(slots_i)
        items, cnt = req["before"][i]
        plen = np.asarray([prompt_len[it] for it in items])
        r["windows"] += 1
        r["steps"] += nd
        r["row_steps"] += nd * B
        r["ctx_row_steps"] += int(sum((plen + cnt + t).sum() for t in range(nd)))
        r["tokens"] += nd * B
        r["exits"] += int((np.asarray(exits) >= 0).sum())
        r["active_ramps"] += len(act) * nd
        r["ramp_calls"] += (gather_slots if len(act) else 0) * nd
        r["empty_active"] += int(len(act) == 0)
        for k, site in enumerate(act):
            a = r["agree"].setdefault(int(site), [0, 0])
            a[0] += int((labels[:, k, :] == finals).sum())
            a[1] += finals.size
    return r


def host_gaps(tl, t0, t1, gc_spans=()):
    """Where the host's time inside [t0, t1] went, to compare a slow run
    with a fast one: each call kind's total and longest (s), the engine's
    own time between calls (total and longest, with the kinds of call on
    either side of the longest), and the garbage collector's pauses."""
    names = {START: "start", WINDOW: "step_multi", OBSERVE: "observe", FREE: "free"}
    idx = np.nonzero((tl.t0[: tl.n] >= t0) & (tl.t1[: tl.n] <= t1))[0]
    out = {}
    for k, name in names.items():
        d = (tl.t1[idx] - tl.t0[idx])[tl.kind[idx] == k]
        out[name] = (float(d.sum()), float(d.max()) if len(d) else 0.0, int(len(d)))
    if len(idx) > 1:
        gap = tl.t0[idx[1:]] - tl.t1[idx[:-1]]
        j = int(np.argmax(gap))
        out["between"] = (float(gap.sum()), float(gap[j]),
                          f"{names[int(tl.kind[idx[j]])]}>{names[int(tl.kind[idx[j + 1]])]}")
    g = [(b - a, gen) for a, b, gen in gc_spans if a >= t0 and b <= t1]
    out["gc"] = (float(sum(x for x, _ in g)), max((x for x, _ in g), default=0.0), len(g),
                 sum(1 for _, gen in g if gen == 2))
    return out
