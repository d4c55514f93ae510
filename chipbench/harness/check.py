"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and holding the longest and every prompt length among
them, is run through the plain reference once each: the prompt followed
by the tokens the program fed back (its final head's greedy tokens). At
every served position the reference's logits judge what the program
produced there:

  * ``final_gap``: how far below the reference's best logit lies the
    logit of the token the program's final head chose (the prefill's
    first token included);
  * ``ramp_gap``: the same for every active ramp head's label, under the
    reference's head at that ramp's site (the labels a ramp releases
    when it exits are among them).

A gap is 0 where the program's token is the reference's best. Over the
sample each kind gives its widest gap, its mean gap, and the share of
positions that differ; the configuration's limits say which are
compared. The widest gap catches a single token gone wrong; the mean gap
grows with the square of the error in the logits (how often a token
flips, times by how much), so it parts a lower precision from the
program's own rounding further than the widest gap does. The control
puts a lower precision in the program's place: the reference's own
weights rounded to int8 or fp8, read at the same positions by the token
that precision puts first.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from harness.traffic import seed_rng


def sample(done, n_tokens, prompt_len, seed, *, min_tokens: int, max_requests: int) -> List[int]:
    """The longest finished request, then one of each prompt length not yet
    in the sample, then others, each in an order drawn from the seed, until
    ``min_tokens`` served tokens or ``max_requests``."""
    done = [int(i) for i in done]
    if not done:
        return []
    longest = max(done, key=lambda i: (n_tokens[i], -i))
    out, total = [longest], int(n_tokens[longest])
    rest = [done[j] for j in seed_rng(seed, 4).permutation(len(done)) if done[j] != longest]
    lengths = {int(prompt_len[longest])}
    for i in rest:
        if len(out) < max_requests and int(prompt_len[i]) not in lengths:
            out.append(i)
            lengths.add(int(prompt_len[i]))
            total += int(n_tokens[i])
    for i in rest:
        if total >= min_tokens or len(out) >= max_requests:
            break
        if i not in out:
            out.append(i)
            total += int(n_tokens[i])
    return out


def ramp_records(tl, req, item):
    """(token index, active ramp indices, their labels) of every decode
    step that produced a token for ``item``."""
    out = []
    for i, (items, cnt) in req["before"].items():
        if item not in items:
            continue
        col = items.index(item)
        _, act, labels, _, _ = tl.payload[i]
        for t in range(labels.shape[0]):
            out.append((int(cnt[col]) + t, tuple(act), labels[t, : len(act), col]))
    return out


def gaps(ref, prompt, finals, records, quant: Optional[str] = None) -> Dict[str, np.ndarray]:
    """Per-position gaps of one request. ``finals``: the program's final
    head tokens (n,); ``records``: its ramp labels. With ``quant`` the
    gaps are of the tokens the control puts first, read at the same
    positions."""
    import jax.numpy as jnp

    n = len(finals)
    plen = len(prompt)
    seq = np.concatenate([prompt, np.asarray(finals[:-1], np.int32)])
    pos = np.arange(plen - 1, plen - 1 + n)
    used = sorted({j for _, act, _ in records for j in act})
    final, ramps = ref.logits(seq, pos, used)
    out = {}
    if quant is None:
        tok = jnp.asarray(np.asarray(finals, np.int32))
    else:
        cf, cr = ref.logits(seq, pos, used, quant=quant)
        tok = jnp.argmax(cf, axis=-1)
    out["final"] = np.asarray(jnp.max(final, -1) - jnp.take_along_axis(final, tok[:, None], 1)[:, 0])
    rg = []
    for j in used:
        rows = [(ti, lab[list(act).index(j)]) for ti, act, lab in records if j in act]
        ti = np.asarray([r[0] for r in rows])
        lg = ramps[j][ti]
        if quant is None:
            lab = jnp.asarray(np.asarray([r[1] for r in rows], np.int32))
        else:
            lab = jnp.argmax(cr[j][ti], axis=-1)
        rg.append(np.asarray(jnp.max(lg, -1) - jnp.take_along_axis(lg, lab[:, None], 1)[:, 0]))
    out["ramp"] = np.concatenate(rg) if rg else np.zeros(0)
    out["scale"] = float(jnp.max(jnp.abs(final)))
    return out


def readings(per_request: List[Dict[str, np.ndarray]]) -> Dict[str, Optional[float]]:
    """Per kind of token (``final``, ``ramp``) over every sampled position:
    the widest gap (``_gap_max``), the mean gap (``_gap_mean``) and the
    share of positions, in %, where the token is not the reference's best
    (``_flips``)."""
    out: Dict[str, Optional[float]] = {}
    for kind in ("final", "ramp"):
        x = np.concatenate([g[kind] for g in per_request]) if per_request else np.zeros(0)
        n = len(x)
        out[f"{kind}_gap_max"] = float(x.max()) if n else None
        out[f"{kind}_gap_mean"] = float(x.mean()) if n else None
        out[f"{kind}_flips"] = 100.0 * float((x > 0).mean()) if n else None
        out[f"{kind}_positions"] = int(n)
    out["logit_scale"] = max((g["scale"] for g in per_request), default=None)
    return out


def verdict(read: Dict[str, Optional[float]], limits: Dict[str, float]) -> bool:
    """Correct where every compared number is present and within its
    limit."""
    return all(read.get(k) is not None and read[k] <= v for k, v in limits.items())
