"""The one traffic generator. A traffic file holds only parameters.

A ``backlog`` mix is a queue present at t=0. It is built from decks: one
deck holds every request size of the mix exactly once (prompt lengths by
``prompt_counts``, output lengths at the deck's evenly spaced quantiles
of a clipped log-normal), and each deck is dealt in an order drawn from
the seed. Every seed therefore serves the same sizes; only their order,
their pairing and the prompt tokens change. The backlog is as long as
the caller asks, and a longer one only appends decks, so the requests a
run serves do not depend on how long the backlog was made.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist
from typing import List, Tuple

import numpy as np


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    """A generator for one use of the seed (any non-negative integer)."""
    return np.random.default_rng([int(seed), int(stream)])


def output_lengths(traffic: dict) -> List[int]:
    """The deck's output lengths (tokens per request, the first included)."""
    o = traffic["output_tokens"]
    n = int(traffic["deck"])
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        v = round(o["lognormal_median"] * float(np.exp(o["lognormal_sigma"] * z)))
        out.append(int(min(o["max"], max(o["min"], v))))
    return out


def prompt_lengths(traffic: dict) -> List[int]:
    lens, counts = traffic["prompt_lengths"], traffic["prompt_counts"]
    if sum(counts) != int(traffic["deck"]):
        raise ValueError(f"prompt_counts {counts} do not fill a deck of {traffic['deck']}")
    return [int(L) for L, c in zip(lens, counts) for _ in range(int(c))]


@dataclasses.dataclass
class Backlog:
    prompt_len: np.ndarray  # (N,) int
    n_tokens: np.ndarray  # (N,) int, tokens to serve, the prefill's first included
    prompts: np.ndarray  # (N, max prompt length) int32; row i's first prompt_len[i] count

    def __len__(self) -> int:
        return len(self.prompt_len)


def sizes(traffic: dict, seed: int, n: int) -> Tuple[np.ndarray, np.ndarray]:
    """Prompt and output lengths of the first ``n`` requests."""
    P, O = np.asarray(prompt_lengths(traffic)), np.asarray(output_lengths(traffic))
    rng = seed_rng(seed, 1)
    pl, nt = [], []
    while len(pl) < n:
        pl.extend(P[rng.permutation(len(P))])
        nt.extend(O[rng.permutation(len(O))])
    return np.asarray(pl[:n], np.int64), np.asarray(nt[:n], np.int64)


def make_backlog(traffic: dict, seed: int, n: int, vocab: int) -> Backlog:
    pl, nt = sizes(traffic, seed, n)
    width = max(traffic["prompt_lengths"])
    prompts = seed_rng(seed, 2).integers(0, vocab, (n, width), dtype=np.int32)
    return Backlog(pl, nt, prompts)


def warmup_waves(traffic: dict, slots: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """Set-up traffic that compiles every shape the mix uses and no other:
    each wave fills every slot, every prompt length appears in it, and all
    its requests end in the same sync window, so no smaller row bucket is
    ever stepped. ``warmup_output_tokens`` picks the window lengths."""
    lens = traffic["prompt_lengths"]
    pl = np.asarray([lens[i % len(lens)] for i in range(slots)], np.int64)
    return [(pl, np.full(slots, int(n), np.int64)) for n in traffic["warmup_output_tokens"]]
