"""Model runners: execute the real (tiny, CPU-trained) models per batch and
stream ramp records to the controller.

On hardware this is the accelerator side: a single jitted program computes
the full model + K gathered ramp heads; only ~KB stat arrays (top-1 label,
max-prob, entropy per ramp) travel to the host — never logits. Batches are
padded to power-of-two buckets to bound compilation count.
"""
from __future__ import annotations

import heapq
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing


def _bucket(n: int) -> int:
    b = 1
    while b < n:
        b *= 2
    return b


class PoolExhausted(RuntimeError):
    """Raised when the paged KV pool has no free block for an allocation.
    The allocator checks capacity BEFORE mutating any state, so a failed
    allocation never corrupts the block table."""


class BlockAllocator:
    """Host-side allocator for the paged KV-cache pool.

    The device pool holds ``n_blocks + 1`` physical blocks: block 0 is
    RESERVED as the trash block — bucket-padding rows point their zeroed
    table rows at it, so their (discarded) scatters land in memory no live
    slot ever reads. Allocatable ids are ``1..n_blocks``; the free heap
    always hands out the lowest id, so identical schedules produce
    identical tables (determinism the equivalence harness relies on).

    Physical blocks are REFCOUNTED: ``alloc`` hands out private blocks
    (refcount 1), ``share`` maps an already-live block into another slot's
    table (refcount += 1 — N slots with a common prompt prefix reference
    ONE physical block set), and the prefix cache holds references via
    ``pin``/``unpin``. A block returns to the free heap only when its last
    reference drops. ``cow`` implements copy-on-write: it swaps one table
    entry for a fresh private block so the caller can copy-then-mutate
    without touching the shared original.

    Invariants (asserted by the property tests):
      * every table entry (and every pinned id) references a live block;
      * ``refcount.sum() == sum(owned) + pins`` across any schedule;
      * ``n_free + (refcount > 0).sum() == n_blocks`` — no block is both
        free and referenced, none leaks;
      * allocation at exhaustion raises ``PoolExhausted`` atomically —
        no table/free-list/refcount mutation happens on the failing call.
    """

    def __init__(self, n_blocks: int, max_blocks_per_slot: int, n_slots: int = 0):
        if n_blocks < 1:
            raise ValueError(f"n_blocks must be >= 1, got {n_blocks}")
        self.n_blocks = n_blocks
        self.max_blocks = max_blocks_per_slot
        self._free = list(range(1, n_blocks + 1))  # min-heap of free ids
        heapq.heapify(self._free)
        self.table = np.zeros((n_slots, max_blocks_per_slot), np.int32)
        self.owned = np.zeros(n_slots, np.int32)
        self.refcount = np.zeros(n_blocks + 1, np.int32)  # per physical block
        self.pins = 0  # live cache (non-slot) references
        self.peak_blocks = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def live_blocks(self) -> int:
        return self.n_blocks - len(self._free)

    def grow_slots(self, n_slots: int) -> None:
        add = n_slots - self.table.shape[0]
        if add > 0:
            self.table = np.concatenate(
                [self.table, np.zeros((add, self.max_blocks), np.int32)]
            )
            self.owned = np.concatenate([self.owned, np.zeros(add, np.int32)])

    def grow_pool(self, n_blocks: int) -> None:
        """Extend the pool with fresh block ids (existing ownership kept)."""
        if n_blocks > self.n_blocks:
            self.refcount = np.concatenate(
                [self.refcount, np.zeros(n_blocks - self.n_blocks, np.int32)]
            )
        for b in range(self.n_blocks + 1, n_blocks + 1):
            heapq.heappush(self._free, b)
        self.n_blocks = max(self.n_blocks, n_blocks)

    def require(self, n: int) -> None:
        """Check ``n`` free blocks exist WITHOUT claiming anything — the
        all-or-nothing precondition for multi-slot claims."""
        if len(self._free) < n:
            raise PoolExhausted(
                f"paged KV pool exhausted: need {n} block(s), "
                f"{len(self._free)}/{self.n_blocks} free"
            )

    def alloc(self, slot: int, n: int = 1) -> List[int]:
        """Claim ``n`` private blocks for ``slot`` (atomic: all or nothing)."""
        if self.owned[slot] + n > self.max_blocks:
            raise ValueError(
                f"slot {slot} would exceed max_blocks={self.max_blocks}"
            )
        self.require(n)
        ids = [heapq.heappop(self._free) for _ in range(n)]
        k = int(self.owned[slot])
        self.table[slot, k : k + n] = ids
        self.owned[slot] += n
        self.refcount[ids] = 1
        self.peak_blocks = max(self.peak_blocks, self.live_blocks)
        return ids

    def alloc_pinned(self, n: int) -> List[int]:
        """Claim ``n`` blocks under a cache (non-slot) reference — the
        read-only pinned pages (cross-attention encoder KV) the runner
        owns directly rather than through a slot's table row. They are
        prefilled once, never appended, and freed via ``unpin``. Atomic:
        all or nothing."""
        self.require(n)
        ids = [heapq.heappop(self._free) for _ in range(n)]
        self.refcount[ids] = 1
        self.pins += n
        self.peak_blocks = max(self.peak_blocks, self.live_blocks)
        return ids

    def share(self, slot: int, ids: Sequence[int]) -> None:
        """Map already-live blocks into ``slot``'s table (prefix sharing):
        the slot references the SAME physical blocks, refcount += 1 each."""
        if not ids:
            return
        if self.owned[slot] + len(ids) > self.max_blocks:
            raise ValueError(
                f"slot {slot} would exceed max_blocks={self.max_blocks}"
            )
        for b in ids:
            if not (1 <= b <= self.n_blocks) or self.refcount[b] < 1:
                raise ValueError(f"cannot share non-live block {b}")
        k = int(self.owned[slot])
        self.table[slot, k : k + len(ids)] = ids
        self.owned[slot] += len(ids)
        for b in ids:
            self.refcount[b] += 1

    def cow(self, slot: int, idx: int) -> Tuple[int, int]:
        """Copy-on-write: replace ``slot``'s ``idx``-th table entry with a
        fresh private block and drop the reference on the old one. Returns
        ``(old_id, new_id)`` — the caller copies the block's contents on
        device before writing. Atomic: raises before any mutation."""
        self.require(1)
        old = int(self.table[slot, idx])
        new = heapq.heappop(self._free)
        self.refcount[new] = 1
        self.table[slot, idx] = new
        self._deref(old)
        self.peak_blocks = max(self.peak_blocks, self.live_blocks)
        return old, new

    def pin(self, b: int) -> None:
        """Take a cache (non-slot) reference on a live block."""
        if not (1 <= b <= self.n_blocks) or self.refcount[b] < 1:
            raise ValueError(f"cannot pin non-live block {b}")
        self.refcount[b] += 1
        self.pins += 1

    def unpin(self, b: int) -> None:
        """Drop a cache reference; the block frees once nothing else holds it."""
        self.pins -= 1
        self._deref(b)

    def _deref(self, b: int) -> None:
        self.refcount[b] -= 1
        if self.refcount[b] == 0:
            heapq.heappush(self._free, b)

    def release_tail(self, slot: int, keep: int) -> None:
        """Drop ``slot``'s table entries beyond the first ``keep`` — a sync
        window that terminated early unwinds its over-claimed appends here,
        restoring the exact allocator state the per-step path would hold.
        ``peak_blocks`` is deliberately NOT rewound: it records the
        transient high-water mark the window really reached."""
        k = int(self.owned[slot])
        if keep >= k:
            return
        for b in self.table[slot, keep:k]:
            self._deref(int(b))
        self.table[slot, keep:k] = 0
        self.owned[slot] = keep

    def free_slot(self, slot: int) -> None:
        """Drop every reference ``slot`` holds (blocks free at refcount 0)."""
        k = int(self.owned[slot])
        for b in self.table[slot, :k]:
            self._deref(int(b))
        self.table[slot, :] = 0  # stale entries must stay valid pool ids
        self.owned[slot] = 0

    def owned_ids(self, slot: int) -> List[int]:
        return [int(b) for b in self.table[slot, : int(self.owned[slot])]]


class PrefixCache:
    """Host-side prompt-prefix trie over the paged KV pool.

    Edges are full ``block_size``-token chunks (keyed by their raw bytes);
    a node pins the physical block holding that chunk's KV, so N prompts
    sharing a prefix resolve to ONE block chain. A whole-prompt entry
    additionally records the partial tail block (when the prompt doesn't
    end on a block boundary) plus the prompt's greedy first token — a
    fully cached prompt starts with ZERO device work (TTFT ~ host time).

    The cache holds one ``pin`` reference per cached block; slots that hit
    ``share`` the same ids. When the pool runs dry, ``evict_for`` unpins
    LRU leaf entries whose block nobody else references (refcount == 1),
    so eviction can never yank a block from under a live slot — and never
    strands a parent, since any slot using a child's chain walked (and
    shares) every ancestor too.
    """

    def __init__(self, alloc: BlockAllocator, block_size: int):
        self._alloc = alloc
        self.bs = int(block_size)
        self._root = {"children": {}, "block": 0, "tick": 0, "tails": {}, "first": None}
        self._tick = 0
        self.hits = 0
        self.tokens_saved = 0
        self.blocks_shared = 0  # cumulative blocks a lookup let a slot skip
        self.evictions = 0

    def lookup(self, toks: np.ndarray, limit: Optional[int] = None):
        """Longest cached cover of ``toks[:limit]`` in whole blocks:
        returns ``(block_ids, n_covered, first_tok)``. ``first_tok`` is
        non-None only on a whole-prompt hit (tail block included)."""
        toks = np.asarray(toks)
        S = len(toks) if limit is None else min(len(toks), int(limit))
        self._tick += 1
        node, ids, m = self._root, [], 0
        while (m + 1) * self.bs <= S:
            child = node["children"].get(toks[m * self.bs : (m + 1) * self.bs].tobytes())
            if child is None:
                break
            child["tick"] = self._tick
            ids.append(child["block"])
            node, m = child, m + 1
        covered = m * self.bs
        if covered == S and node is not self._root and node["first"] is not None:
            return ids, S, node["first"]
        if m == S // self.bs and S % self.bs and S == len(toks):
            tail = node["tails"].get(toks[covered:].tobytes())
            if tail is not None:
                tail["tick"] = self._tick
                return ids + [tail["block"]], S, tail["first"]
        return ids, covered, None

    def register(self, toks: np.ndarray, ids: Sequence[int], first_tok: int) -> None:
        """Record a fully prefilled prompt: ``ids`` are the owning slot's
        blocks in order. New chunks pin their block; chunks already cached
        keep their first-registered block (the slot shares it anyway)."""
        toks = np.asarray(toks)
        S = len(toks)
        self._tick += 1
        node = self._root
        for m in range(S // self.bs):
            key = toks[m * self.bs : (m + 1) * self.bs].tobytes()
            child = node["children"].get(key)
            if child is None:
                child = {"children": {}, "block": int(ids[m]), "tick": self._tick,
                         "tails": {}, "first": None}
                self._alloc.pin(int(ids[m]))
                node["children"][key] = child
            child["tick"] = self._tick
            node = child
        if S % self.bs:
            key = toks[S - S % self.bs :].tobytes()
            tail = node["tails"].get(key)
            if tail is None:
                node["tails"][key] = {"block": int(ids[S // self.bs]),
                                      "first": int(first_tok), "tick": self._tick}
                self._alloc.pin(int(ids[S // self.bs]))
            else:
                tail["tick"] = self._tick
        elif node is not self._root and node["first"] is None:
            node["first"] = int(first_tok)

    def _evictable(self):
        """All LRU-evictable entries: tails, plus chunk nodes with no
        descendants, whose block only the cache still references."""
        out = []
        stack = [self._root]
        while stack:
            node = stack.pop()
            for key, tail in node["tails"].items():
                if self._alloc.refcount[tail["block"]] == 1:
                    out.append((tail["tick"], 1, key, node, tail))
            for key, ch in node["children"].items():
                if (not ch["children"] and not ch["tails"]
                        and self._alloc.refcount[ch["block"]] == 1):
                    out.append((ch["tick"], 0, key, node, ch))
                stack.append(ch)
        return out

    def evict_for(self, n: int) -> None:
        """Unpin least-recently-used cache-only entries until ``n`` blocks
        are free (or nothing evictable remains — the caller's ``require``
        then raises). Deterministic: ties break on kind then key bytes."""
        while self._alloc.n_free < n:
            cands = self._evictable()
            if not cands:
                return
            _, kind, key, parent, entry = min(cands, key=lambda c: c[:3])
            if kind == 1:
                del parent["tails"][key]
            else:
                del parent["children"][key]
            self._alloc.unpin(entry["block"])
            self.evictions += 1

    def clear(self) -> None:
        """Drop every cache reference (slots keep theirs)."""
        stack = [self._root]
        while stack:
            node = stack.pop()
            for tail in node["tails"].values():
                self._alloc.unpin(tail["block"])
            for ch in node["children"].values():
                self._alloc.unpin(ch["block"])
                stack.append(ch)
        self._root = {"children": {}, "block": 0, "tick": 0, "tails": {}, "first": None}


class SyntheticRunner:
    """Profile-only serving: deterministic ramp records without a model.

    A fixed fraction of items is "easy" — confidently predictable from
    ``exit_site`` onward — so controllers activate ramps and exit traffic
    exactly as with a trained model, at zero model cost. Used by the
    scale-out demos/benchmarks where training one model per replica-count
    sweep would dominate runtime.
    """

    def __init__(self, n_sites: int, exit_site: int, easy_frac: float = 0.7,
                 n_classes: int = 17):
        self.n_sites = n_sites
        self.exit_site = exit_site
        self.easy_frac = easy_frac
        self.n_classes = n_classes

    def infer(self, items: np.ndarray, active: Sequence[int]):
        items = np.asarray(items)  # repro: allow[host-sync] — host input normalization — items never lives on device
        k = len(active)
        B = len(items)
        final = (items % self.n_classes).astype(np.int64)
        easy = (items % 100) < self.easy_frac * 100
        # hard items DISAGREE with the original model at every ramp (like
        # SyntheticDecodeRunner): an over-opened threshold that releases
        # them costs accuracy, exactly as with a trained model. Tiling the
        # final label into every row made hard exits free.
        wrong = (final + 1) % self.n_classes
        labels = np.tile(wrong, (max(k, 1), 1))
        unc = np.full((max(k, 1), B), 0.9, np.float32)
        for j, s in enumerate(sorted(active)):
            if s >= self.exit_site:
                labels[j] = np.where(easy, final, wrong)
                unc[j] = np.where(easy, 0.02, 0.9)
        if k == 0:
            return labels[:0], unc[:0], final
        return labels[:k], unc[:k], final

    def vanilla_labels(self, n: int) -> np.ndarray:
        return np.arange(n, dtype=np.int64) % self.n_classes


class ClassifierRunner:
    """ResNet / BERT-style classifier serving (the paper's workloads)."""

    def __init__(self, model, params, data: np.ndarray, max_slots: int = 8):
        self.model = model
        self.params = params
        self.data = data  # (N, ...) images or token sequences
        self.max_slots = max_slots
        self._fns = {}
        self.compiles = 0  # ramp-set changes recompile (paper: model re-upload)
        self.noramp_compiles = 0  # no-ramp (vanilla) variant compiles

    def _fn(self, bs: int, act: Optional[tuple]):
        """act=None compiles the no-ramp (vanilla) variant: with zero active
        ramps the model must not execute-and-discard a ramp head — vanilla
        serving would silently pay one ramp of compute per batch."""
        key = (bs, act)
        if key not in self._fns:
            m = self.model
            if act is None:
                # no-ramp (vanilla) compiles are NOT ramp-set changes: they
                # must not inflate `compiles`, the "ramp-set change
                # recompile" stat the paper's overhead story rests on
                self.noramp_compiles += 1

                @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
                def f0(params, x):
                    return m.forward(params, x, active_sites=None)["final"]["label"]

                self._fns[key] = f0
            else:
                self.compiles += 1

                @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
                def f(params, x):
                    outs = m.forward(params, x, active_sites=list(act))
                    return (
                        outs["ramps"]["label"],
                        1.0 - outs["ramps"]["maxprob"],
                        outs["final"]["label"],
                    )

                self._fns[key] = f
        return self._fns[key]

    def infer(self, items: np.ndarray, active: Sequence[int]):
        bs = _bucket(len(items))
        idx = np.pad(items, (0, bs - len(items)), mode="edge")
        x = jnp.asarray(self.data[idx])
        act = tuple(sorted(active))
        if len(act) > self.max_slots:
            # silently truncating would return fewer record rows than the
            # controller asked for — rows land against the wrong sites
            raise ValueError(
                f"active ramp set has {len(act)} sites, max_slots={self.max_slots}"
            )
        k = len(act)
        if k == 0:
            final = np.asarray(self._fn(bs, None)(self.params, x))[: len(items)]  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
            return np.zeros((0, len(items)), np.int64), np.zeros((0, len(items)), np.float32), final
        labels, unc, final = self._fn(bs, act)(self.params, x)
        labels = np.asarray(labels)[:, : len(items)]  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
        unc = np.asarray(unc)[:, : len(items)]  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
        final = np.asarray(final)[: len(items)]  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
        return labels[:k], unc[:k].astype(np.float32), final

    def vanilla_labels(self, n: Optional[int] = None) -> np.ndarray:
        """Original-model labels for the whole stream (accuracy ground truth)."""
        # `n or len` would remap an explicit n=0 to the whole dataset
        n = n if n is not None else len(self.data)
        if n < 1:
            return np.zeros(0, np.int64)
        out = []
        for lo in range(0, n, 256):
            hi = min(lo + 256, n)
            idx = np.arange(lo, hi)
            _, _, f = self.infer(idx, [])  # no-ramp variant: zero ramp compute
            out.append(f)
        return np.concatenate(out)


class LMTokenRunner:
    """Per-token early-exit serving for decoder LMs: each request is a
    context; the served result is the next token (prefill path)."""

    def __init__(self, model, params, data: np.ndarray, max_slots: int = 8):
        self.model = model
        self.params = params
        self.data = data  # (N, S) int32 contexts
        self.max_slots = max_slots
        self._fns = {}
        self._fns0 = {}  # no-ramp (vanilla) variants

    def _fn_noramp(self, bs: int):
        if bs not in self._fns0:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def f0(params, toks):
                _, outs = m.prefill(
                    params, toks, active_sites=None, with_cache=False, moe_impl="dense"
                )
                lab = outs["final"]["label"]
                return lab[:, 0] if lab.ndim == 2 else lab

            self._fns0[bs] = f0
        return self._fns0[bs]

    def _fn(self, bs: int):
        if bs not in self._fns:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def f(params, toks, active):
                _, outs = m.prefill(
                    params, toks, active_sites=active, with_cache=False, moe_impl="dense"
                )
                return (
                    outs["ramps"]["label"][:, :, 0] if outs["ramps"]["label"].ndim == 3 else outs["ramps"]["label"],
                    1.0 - (outs["ramps"]["maxprob"][:, :, 0] if outs["ramps"]["maxprob"].ndim == 3 else outs["ramps"]["maxprob"]),
                    outs["final"]["label"][:, 0] if outs["final"]["label"].ndim == 2 else outs["final"]["label"],
                )

            self._fns[bs] = f
        return self._fns[bs]

    def infer(self, items: np.ndarray, active: Sequence[int]):
        bs = _bucket(len(items))
        idx = np.pad(items, (0, bs - len(items)), mode="edge")
        toks = jnp.asarray(self.data[idx])
        # sort (like ClassifierRunner): the controller consumes record rows
        # in ascending-site order, so an unsorted caller set must not leak
        # row misalignment into the window
        act = sorted(active)
        if len(act) > self.max_slots:
            raise ValueError(
                f"active ramp set has {len(act)} sites, max_slots={self.max_slots}"
            )
        k = len(act)
        if k == 0:
            final = np.asarray(self._fn_noramp(bs)(self.params, toks))[: len(items)]  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
            return np.zeros((0, len(items)), np.int64), np.zeros((0, len(items)), np.float32), final
        pad_act = act + [act[-1]] * (self.max_slots - len(act))
        labels, unc, final = self._fn(bs)(
            self.params, toks, jnp.asarray(pad_act, jnp.int32)
        )
        final = np.asarray(final)[: len(items)]  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
        return (
            np.asarray(labels)[:k, : len(items)],  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
            np.asarray(unc)[:k, : len(items)].astype(np.float32),  # repro: allow[host-sync] — sanctioned record drain: one stats pull per dispatch
            final,
        )

    def vanilla_labels(self, n: Optional[int] = None) -> np.ndarray:
        # `n or len` would remap an explicit n=0 to the whole dataset
        n = n if n is not None else len(self.data)
        if n < 1:
            return np.zeros(0, np.int64)
        out = []
        for lo in range(0, n, 128):
            idx = np.arange(lo, min(lo + 128, n))
            _, _, f = self.infer(idx, [])  # no-ramp variant: zero ramp compute
            out.append(f)
        return np.concatenate(out)


class DecodeRunner:
    """Real-model generative runner: drives ``model.decode`` with ONE
    jitted dispatch per engine step over a single batched slot cache,
    streaming one ramp record per in-flight token to the controller (the
    paper's generative per-token exits).

    Records are replay-complete — the full model and the gathered ramp
    heads run for every token, because the controller needs agreement
    labels to adapt — while serving *time* is simulated by the engine from
    the latency profile (truncated compute + deferred KV catch-up). The
    decoded trajectory follows the original model's greedy tokens so
    per-token agreement against the vanilla stream stays measurable even
    when a ramp disagrees.

    The cache is one batched tree keyed by slot index: ``start`` prefills
    into a slot row, ``step(slots, active)`` gathers the live rows, runs a
    single jitted decode with per-row positions (``model.decode`` takes
    ``pos: int32[B]``), and scatters the rows back; ``free`` just releases
    the row. Continuous batching admits/retires at step boundaries, so row
    positions diverge — per-row cache write indices are what make the
    shared cache sound. Live rows are padded to a power-of-two bucket with
    FREE rows (distinct indices, so the scatter is collision-free and the
    padded rows hold garbage no one reads), bounding compile count at
    log2(n_slots) shapes. Batch-level timing comes from the profile, not
    from here.

    With a ``decode_attn='paged*'`` model config the slot cache is PAGED:
    one global pool of ``kv_blocks`` fixed-size blocks (``kv_block_size``
    key/value tokens each) plus a per-slot block table, managed by a
    host-side ``BlockAllocator``. ``start`` claims ``ceil(prompt_len /
    block_size)`` blocks and scatters the prefill KV into them, ``step``
    appends a block only when a slot's current block fills, and ``free``
    returns the slot's blocks to the pool — KV memory scales with LIVE
    TOKENS instead of ``n_slots * max_len``, at the same one dispatch per
    engine step. ``kv_blocks=None`` auto-sizes the pool to full slot
    capacity (the contiguous equivalent); a smaller explicit pool admits
    more slots than contiguous memory would allow, and exhausting it
    raises ``PoolExhausted`` cleanly.
    """

    def __init__(self, model, params, prompts: np.ndarray, *, max_new_tokens: int = 64,
                 max_slots: int = 8, n_slots: Optional[int] = None,
                 kv_block_size: int = 16, kv_blocks: Optional[int] = None,
                 prefix_cache: bool = False):
        self.model = model
        self.params = params
        self.prompts = np.asarray(prompts, np.int32)  # (N, S)
        self.max_new = max_new_tokens
        self.max_slots = max_slots  # K ramp gather slots (not decode rows)
        self.n_sites = len(model.sites)
        self.dispatches = 0  # jitted decode-step calls (1/step, not 1/slot)
        # sync windows that ran fewer steps than asked, by reason: cache
        # headroom cut the window, or every row exited before its end
        self.short_windows = {"headroom": 0, "early_end": 0}
        # paged runners: pages the decode kernel attends (live rows' pages
        # up to their positions) against the table pages it could walk
        self.kv_pages = {"attended": 0, "table": 0}
        self._cache = None  # batched slot cache; rows grown on demand
        self._rows = 0 if n_slots is None else _bucket(max(n_slots, 1))
        self._cache_len = self.prompts.shape[1] + self.max_new
        self._live = set()
        self._pos = np.zeros(0, np.int64)
        self._tok = np.zeros(0, np.int64)
        self._axes: Optional[Tuple[int, ...]] = None  # per-leaf batch axis
        self._pf = None
        self._pf_paged = {}  # paged prefill programs, keyed by token count
        self._pf_progress = {}  # slot -> item for in-flight chunked prefills
        self._dec = None
        self._dec0 = None  # no-ramp (vanilla) decode variant
        self._decm = {}  # multi-step (sync window) programs, keyed by n_max
        self._decm0 = {}  # no-ramp multi-step variant, keyed by n_max
        # device-resident exit thresholds: pushed once per sync window and
        # ONLY when the controller actually changed them — between syncs
        # the device decides exits from this (deliberately stale) copy
        self._thr_host = None
        self._thr_dev = None
        # -- paged-KV state (decode_attn='paged'|'paged-kernel'|'paged-interpret')
        self.paged = str(getattr(model.cfg, "decode_attn", "")).startswith("paged")
        self._bs_blk = int(kv_block_size)
        self._kv_blocks = kv_blocks
        if self.paged and self._bs_blk < 1:
            raise ValueError(f"paged decode needs kv_block_size >= 1, got {kv_block_size}")
        if prefix_cache and not self.paged:
            raise ValueError("prefix_cache requires a paged decode_attn config")
        if prefix_cache and not getattr(model, "paged_sharing_ok", True):
            # sharing moves TOKEN pages between tables; mamba state pages,
            # ring (position-aliased) pages and pinned xkv pages don't
            # share — refusing here beats silently corrupting slots later
            raise ValueError(
                "prefix_cache: prefix sharing/CoW is unsound for this model "
                "family (recurrent-state, ring-window, or cross-attention "
                "pages cannot be shared between slots)"
            )
        # kv_block_size is meaningless for contiguous runners (0 documents
        # "contiguous" at the CLI) — don't let it poison the ceil below
        self._max_blocks = -(-self._cache_len // self._bs_blk) if self.paged else 0
        self._alloc: Optional[BlockAllocator] = None
        self._pool_axes: Optional[Tuple[int, ...]] = None  # per-leaf pool axis
        # per-leaf page kinds ('tokens' | 'state' | 'xkv') steering the
        # prefill scatter and swap gather/scatter branches, plus the count
        # of trailing pinned xkv table columns (0 for non-cross plans)
        self._kinds: Optional[Tuple[str, ...]] = (
            tuple(model.paged_cache_kinds(2, self._bs_blk)) if self.paged else None
        )
        self._nbx = (
            int(model.paged_xkv_blocks(self._bs_blk))
            if self.paged and hasattr(model, "paged_xkv_blocks") else 0
        )
        self._xkv_tab = np.zeros((0, self._nbx), np.int32)  # per-slot pinned ids
        self._want_prefix = bool(prefix_cache)
        self._prefix: Optional[PrefixCache] = None  # built with the allocator
        self._copy_blk = None  # jitted whole-block pool copy (CoW)
        self.cow_copies = 0
        self.saved_blocks = 0  # cumulative blocks prefix hits let slots skip
        self.swap_outs = 0
        self.swap_ins = 0
        self.swapped_blocks = 0  # cumulative blocks moved to host buffers

    # -- batched-cache plumbing ---------------------------------------------

    @staticmethod
    def _diff_axes(a, b) -> Tuple[int, ...]:
        """Per-leaf axis where two schema variants disagree — the batch
        (contiguous) or pool (paged) dim: scanned blocks carry a leading
        period dim, prefix/suffix leaves don't."""
        return tuple(
            next(i for i, (x, y) in enumerate(zip(la.shape, lb.shape)) if x != y)
            for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b))
        )

    def _grow_rows(self, rows: int) -> None:
        self._rows = rows
        self._pos = np.concatenate([self._pos, np.zeros(rows - len(self._pos), np.int64)])
        self._tok = np.concatenate([self._tok, np.zeros(rows - len(self._tok), np.int64)])

    def _ensure_rows(self, n: int) -> None:
        """Allocate (or grow) the batched cache to >= n power-of-two rows.
        Growth copies live rows once; steady state never reallocates."""
        if self._cache is not None and n <= self._rows:
            return
        if self.paged:
            self._ensure_rows_paged(n)
            return
        rows = _bucket(max(n, self._rows, 1))
        new = self.model.init_cache(rows, self._cache_len)
        if self._axes is None:
            self._axes = self._diff_axes(
                self.model.cache_schema(1, 2), self.model.cache_schema(2, 2)
            )
        if self._cache is not None:
            old, td = jax.tree.flatten(self._cache)
            new_l = jax.tree.leaves(new)
            new = jax.tree.unflatten(td, [
                jax.lax.dynamic_update_slice_in_dim(nl, ol, 0, axis=ax)
                for nl, ol, ax in zip(new_l, old, self._axes)
            ])
        self._cache = new
        self._grow_rows(rows)

    def _tree_take(self, cache, rows):
        leaves, td = jax.tree.flatten(cache)
        return jax.tree.unflatten(td, [
            jnp.take(l, rows, axis=ax) for l, ax in zip(leaves, self._axes)
        ])

    def _tree_put(self, cache, sub, rows):
        leaves, td = jax.tree.flatten(cache)
        subl = jax.tree.leaves(sub)
        out = []
        for l, s, ax in zip(leaves, subl, self._axes):
            upd = jnp.moveaxis(l, ax, 0).at[rows].set(jnp.moveaxis(s, ax, 0))
            out.append(jnp.moveaxis(upd, 0, ax))
        return jax.tree.unflatten(td, out)

    # -- paged-pool plumbing -------------------------------------------------

    def _ensure_rows_paged(self, n: int) -> None:
        """Grow table rows (and, when ``kv_blocks`` is auto, the block pool)
        to cover >= n power-of-two slots. The pool array holds
        ``n_blocks + 1`` physical blocks — block 0 is the allocator's
        reserved trash block."""
        rows = _bucket(max(n, self._rows, 1))
        nblk = (self._kv_blocks if self._kv_blocks is not None
                else rows * (self._max_blocks + self._nbx))
        if self._alloc is None:
            if self._pool_axes is None:
                self._pool_axes = self._diff_axes(
                    self.model.paged_cache_schema(1, self._bs_blk),
                    self.model.paged_cache_schema(2, self._bs_blk),
                )
            self._alloc = BlockAllocator(nblk, self._max_blocks, rows)
            self._cache = self.model.init_paged_cache(nblk + 1, self._bs_blk)
            if self._want_prefix:
                self._prefix = PrefixCache(self._alloc, self._bs_blk)
        else:
            self._alloc.grow_slots(rows)
            if nblk > self._alloc.n_blocks:
                new = self.model.init_paged_cache(nblk + 1, self._bs_blk)
                old, td = jax.tree.flatten(self._cache)
                new_l = jax.tree.leaves(new)
                self._cache = jax.tree.unflatten(td, [
                    jax.lax.dynamic_update_slice_in_dim(nl, ol, 0, axis=ax)
                    for nl, ol, ax in zip(new_l, old, self._pool_axes)
                ])
                self._alloc.grow_pool(nblk)
        if self._nbx and self._xkv_tab.shape[0] < rows:
            self._xkv_tab = np.concatenate([
                self._xkv_tab,
                np.zeros((rows - self._xkv_tab.shape[0], self._nbx), np.int32),
            ])
        self._grow_rows(rows)

    def cache_bytes(self) -> int:
        """Device bytes held by the KV cache (pool or contiguous rows)."""
        if self._cache is None:
            return 0
        return int(sum(
            l.size * np.dtype(l.dtype).itemsize for l in jax.tree.leaves(self._cache)
        ))

    def kv_stats(self) -> dict:
        out = {"paged": self.paged, "cache_bytes": float(self.cache_bytes())}
        if self.paged and self._alloc is not None:
            out.update(
                block_size=self._bs_blk,
                n_blocks=self._alloc.n_blocks,
                live_blocks=self._alloc.live_blocks,
                peak_blocks=self._alloc.peak_blocks,
                peak_token_capacity=self._alloc.peak_blocks * self._bs_blk,
                shared_blocks=int((self._alloc.refcount > 1).sum()),
                cow_copies=self.cow_copies,
                swap_outs=self.swap_outs,
                swap_ins=self.swap_ins,
                swapped_blocks=self.swapped_blocks,
            )
            if self._prefix is not None:
                out.update(
                    prefix_hits=self._prefix.hits,
                    prefix_tokens_saved=self._prefix.tokens_saved,
                    saved_blocks=self.saved_blocks,
                    prefix_evictions=self._prefix.evictions,
                    pinned_blocks=self._alloc.pins,
                )
        return out

    # -- jitted programs ----------------------------------------------------

    def _prefill_fn(self):
        """Prefill one prompt AND scatter its cache into the slot row —
        one dispatch per admit (`slot` is a traced scalar: no recompile
        per slot id)."""
        if self._pf is None:
            m, cache_len = self.model, self._cache_len

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def pf(params, big, toks, slot):
                cache, outs = m.prefill(
                    params, toks, cache_len=cache_len, active_sites=None,
                    with_cache=True, moe_impl="dense",
                )
                big = self._tree_put(big, cache, slot[None])
                lab = outs["final"]["label"]
                return big, (lab[:, 0] if lab.ndim == 2 else lab)

            self._pf = pf
        return self._pf

    def _decode_fn(self):
        if self._dec is None:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec(params, big, toks, pos, rows, active):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode(
                    params, sub, toks, pos, active_sites=active, moe_impl="dense"
                )
                big = self._tree_put(big, sub, rows)
                return big, (
                    outs["ramps"]["label"],
                    1.0 - outs["ramps"]["maxprob"],
                    outs["final"]["label"],
                )

            self._dec = dec
        return self._dec

    def _decode_fn_noramp(self):
        """Ramp-free decode: with zero active ramps (controller bootstrap /
        budget-busted states) the step must not execute-and-discard ramp
        heads — same fix as the classifier/token runners' no-ramp variants."""
        if self._dec0 is None:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec0(params, big, toks, pos, rows):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode(
                    params, sub, toks, pos, active_sites=None, moe_impl="dense"
                )
                big = self._tree_put(big, sub, rows)
                return big, outs["final"]["label"]

            self._dec0 = dec0
        return self._dec0

    def _prefill_fn_paged(self, n_tokens: Optional[int] = None):
        """Prefill one prompt (or its first ``n_tokens`` — a chunked-prefill
        first chunk) contiguously AND scatter its KV into the slot's claimed
        pool blocks — one dispatch per admit (``blk_ids`` is a traced
        array: no recompile per block assignment). Compiled per distinct
        token count (full prompts and one chunk size in practice)."""
        n_tokens = self.prompts.shape[1] if n_tokens is None else n_tokens
        if n_tokens not in self._pf_paged:
            m, cache_len = self.model, self._cache_len
            bs = self._bs_blk
            nb_pf = -(-n_tokens // bs)
            axes, kinds = self._pool_axes, self._kinds
            nbx = self._nbx

            def scatter(pool, cont, ax, blk_ids, nb):
                # cont: contiguous leaf, batch dim (size 1) at ax, tokens at
                # ax+1; pool: (..., P, bs, ...) with P at ax. Regroup the
                # first nb*bs prefill tokens into blocks and write them
                # to the claimed pool slots.
                x = jnp.moveaxis(cont, ax, 0)[0]
                t = jnp.moveaxis(x, ax, 0)  # tokens first, rest order kept
                need = nb * bs
                if t.shape[0] < need:
                    t = jnp.pad(t, [(0, need - t.shape[0])] + [(0, 0)] * (t.ndim - 1))
                t = t[:need].reshape((nb, bs) + t.shape[1:])
                p2 = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
                p2 = p2.at[blk_ids].set(t.astype(p2.dtype))
                return jnp.moveaxis(p2, (0, 1), (ax, ax + 1))

            def scatter_state(pool, cont, ax, page):
                # per-slot state page (mamba conv/ssm): the whole recurrent
                # state of batch row 0 lands in the slot's FIRST block —
                # the same id token pools use for tokens 0..bs-1; distinct
                # leaves, so the double use never collides.
                x = jnp.moveaxis(cont, ax, 0)[0]
                p2 = jnp.moveaxis(pool, ax, 0)
                return jnp.moveaxis(p2.at[page].set(x.astype(p2.dtype)), 0, ax)

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def pf(params, pools, toks, blk_ids, xkv_ids):
                cache, outs = m.prefill(
                    params, toks, cache_len=cache_len, active_sites=None,
                    with_cache=True, moe_impl="dense",
                )
                leaves, td = jax.tree.flatten(pools)
                cl = jax.tree.leaves(cache)
                out = []
                for p, c, ax, kind in zip(leaves, cl, axes, kinds):
                    if kind == "state":
                        out.append(scatter_state(p, c, ax, blk_ids[0]))
                    elif kind == "xkv":
                        out.append(scatter(p, c, ax, xkv_ids, nbx))
                    else:
                        out.append(scatter(p, c, ax, blk_ids, nb_pf))
                pools = jax.tree.unflatten(td, out)
                lab = outs["final"]["label"]
                return pools, (lab[:, 0] if lab.ndim == 2 else lab)

            self._pf_paged[n_tokens] = pf
        return self._pf_paged[n_tokens]

    def _decode_fn_paged(self):
        if self._dec is None:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec(params, pools, toks, pos, tables, active):
                pools, outs = m.decode(
                    params, pools, toks, pos, active_sites=active,
                    moe_impl="dense", block_tables=tables,
                )
                return pools, (
                    outs["ramps"]["label"],
                    1.0 - outs["ramps"]["maxprob"],
                    outs["final"]["label"],
                )

            self._dec = dec
        return self._dec

    def _decode_fn_paged_noramp(self):
        if self._dec0 is None:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec0(params, pools, toks, pos, tables):
                pools, outs = m.decode(
                    params, pools, toks, pos, active_sites=None,
                    moe_impl="dense", block_tables=tables,
                )
                return pools, outs["final"]["label"]

            self._dec0 = dec0
        return self._dec0

    def _decode_multi_fn(self, n_max: int):
        if n_max not in self._decm:
            m = self.model

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm(params, big, toks, pos, rows, active, thr, n, valid):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode_multi(
                    params, sub, toks, pos, n, n_max=n_max,
                    active_sites=active, thresholds=thr, row_valid=valid,
                    moe_impl="dense",
                )
                big = self._tree_put(big, sub, rows)
                return big, outs

            self._decm[n_max] = decm
        return self._decm[n_max]

    def _decode_multi_fn_noramp(self, n_max: int):
        if n_max not in self._decm0:
            m = self.model

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm0(params, big, toks, pos, rows, n, valid):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode_multi(
                    params, sub, toks, pos, n, n_max=n_max,
                    active_sites=None, row_valid=valid, moe_impl="dense",
                )
                big = self._tree_put(big, sub, rows)
                return big, outs

            self._decm0[n_max] = decm0
        return self._decm0[n_max]

    def _decode_multi_fn_paged(self, n_max: int):
        if n_max not in self._decm:
            m = self.model

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm(params, pools, toks, pos, tables, active, thr, n, valid):
                pools, outs = m.decode_multi(
                    params, pools, toks, pos, n, n_max=n_max,
                    active_sites=active, thresholds=thr, row_valid=valid,
                    moe_impl="dense", block_tables=tables,
                )
                return pools, outs

            self._decm[n_max] = decm
        return self._decm[n_max]

    def _decode_multi_fn_paged_noramp(self, n_max: int):
        if n_max not in self._decm0:
            m = self.model

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm0(params, pools, toks, pos, tables, n, valid):
                pools, outs = m.decode_multi(
                    params, pools, toks, pos, n, n_max=n_max,
                    active_sites=None, row_valid=valid,
                    moe_impl="dense", block_tables=tables,
                )
                return pools, outs

            self._decm0[n_max] = decm0
        return self._decm0[n_max]

    def _copy_block_fn(self):
        """Whole-block pool copy (CoW): duplicate physical block ``src``
        into ``dst`` across every cache leaf — src/dst are traced scalars,
        so one compile covers every copy."""
        if self._copy_blk is None:
            axes = self._pool_axes

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def cp(pools, src, dst):
                leaves, td = jax.tree.flatten(pools)
                out = []
                for l, ax in zip(leaves, axes):
                    m = jnp.moveaxis(l, ax, 0)
                    m = m.at[dst].set(m[src])
                    out.append(jnp.moveaxis(m, 0, ax))
                return jax.tree.unflatten(td, out)

            self._copy_blk = cp
        return self._copy_blk

    # -- prefix sharing / CoW / swap plumbing --------------------------------

    def _reserve(self, n: int) -> None:
        """Guarantee ``n`` free blocks, evicting cache-only prefix entries
        (LRU) if needed; raises ``PoolExhausted`` without mutating slot
        state when even a drained cache can't cover the claim."""
        if self._prefix is not None:
            self._prefix.evict_for(n)
        self._alloc.require(n)

    def _claim_step_blocks(self, slots: Sequence[int], offset: int = 0) -> None:
        """All-or-nothing block claim for one decode-token write per slot:
        totals the appends (slot's current block full) and CoW copies
        (append lands in a block another slot or the prefix cache still
        references) across ALL stepped slots, reserves them in one pass,
        THEN mutates — a mid-loop ``PoolExhausted`` can no longer leave
        earlier slots holding freshly appended blocks.

        ``offset`` claims for the write at ``pos + offset`` instead of
        ``pos``: a sync window pre-claims its N steps as N sequential
        calls with offsets 0..N-1, which replicates the per-step claim
        (and prefix-eviction) order EXACTLY — block-id assignment off the
        min-heap stays bit-identical to N separate ``step`` calls."""
        al, bs = self._alloc, self._bs_blk
        need_app, need_cow, total = [], [], 0
        for s in dict.fromkeys(slots):
            k, p = int(al.owned[s]), int(self._pos[s]) + offset
            na = max(0, p // bs + 1 - k)
            if k + na > al.max_blocks:
                raise ValueError(
                    f"slot {s} would exceed max_blocks={al.max_blocks}"
                )
            if na:
                need_app.append((s, na))
                total += na
            elif al.refcount[al.table[s, p // bs]] > 1:
                need_cow.append((s, p // bs))
                total += 1
        if not total:
            return
        self._reserve(total)
        for s, na in need_app:
            al.alloc(s, na)
        for s, bi in need_cow:
            old, new = al.cow(s, bi)
            self._cache = self._copy_block_fn()(
                self._cache, jnp.int32(old), jnp.int32(new)
            )
            self.cow_copies += 1

    def _free_slot_blocks(self, slot: int) -> None:
        """Release every block reference ``slot`` holds: its token table
        row AND its pinned read-only xkv pages."""
        self._alloc.free_slot(slot)
        if self._nbx and self._xkv_tab[slot, 0]:
            for b in self._xkv_tab[slot]:
                self._alloc.unpin(int(b))
            self._xkv_tab[slot] = 0

    def _claim_xkv(self, slot: int) -> None:
        """Claim ``slot``'s pinned xkv pages (cross-attention encoder KV):
        once per admission, prefilled once, never appended, freed with the
        slot. Raises ``PoolExhausted`` atomically."""
        if not self._nbx or self._xkv_tab[slot, 0]:
            return
        self._reserve(self._nbx)
        self._xkv_tab[slot] = self._alloc.alloc_pinned(self._nbx)

    def _xkv_ids_j(self, slot: int):
        ids = self._xkv_tab[slot] if self._nbx else np.zeros(0, np.int32)
        return jnp.asarray(ids, jnp.int32)

    def _ship_tables(self, rows, zero_lo: int, zero_hi: int):
        """Device block tables for ``rows``: the allocator's token-table
        rows widened by the trailing pinned xkv columns. Rows in
        ``[zero_lo, zero_hi)`` — the FREE bucket-padding rows, whose stale
        entries may reference blocks live slots now own — are redirected
        wholesale to the reserved trash block 0."""
        t = self._alloc.table[rows].copy()
        t[zero_lo:zero_hi] = 0
        if self._nbx:
            x = self._xkv_tab[rows].copy()
            x[zero_lo:zero_hi] = 0
            t = np.concatenate([t, x], axis=1)
        return jnp.asarray(t, jnp.int32)

    def _check_admission_capacity(self) -> None:
        """Admission guard: a slot started now will write ``prompt_len +
        max_new`` tokens into a cache sized at construction time. Refuse
        with a clear error HERE instead of silently overflowing the slot
        tail (contiguous: out-of-range scatters clamp; paged: the table
        walk reads another slot's blocks) — catches stale-capacity hazards
        such as the prompts array being swapped for a longer one after the
        runner was built."""
        plen = int(self.prompts.shape[1])
        need = plen + self.max_new
        if self.paged:
            cap = self._max_blocks * self._bs_blk
            layout = (f"paged capacity {cap} tokens "
                      f"({self._max_blocks} blocks x {self._bs_blk})")
        else:
            cap = self._cache_len
            layout = f"contiguous cache_len {cap}"
        if need > cap:
            raise ValueError(
                f"cannot admit: prompt_len({plen}) + max_new({self.max_new}) "
                f"= {need} tokens exceeds the slot cache capacity — {layout}; "
                "rebuild the runner with a larger max_new_tokens/cache"
            )

    def cached_prefix_tokens(self, item: int) -> int:
        """Prompt tokens of ``item`` already covered by the prefix cache
        (0 without one) — the engine prices prefill on the uncached tail."""
        if self._prefix is None:
            return 0
        _, covered, _ = self._prefix.lookup(self.prompts[item])
        return covered

    def swap_out(self, slot: int) -> dict:
        """Preempt ``slot``: gather its KV blocks into host buffers, drop
        its block references, and retire the slot — the pool space funds
        other streams. Returns an opaque handle for ``swap_in``. Shared
        blocks stay live (the other holders keep them); the handle carries
        their CONTENT, so restore never depends on cache survival."""
        if not self.paged:
            raise ValueError("swap_out requires a paged KV cache")
        if slot not in self._live:
            raise KeyError(f"slot {slot} is not live")
        if slot in self._pf_progress:
            raise KeyError(f"slot {slot} is mid-prefill (cannot swap)")
        ids = self._alloc.owned_ids(slot)
        idx = jnp.asarray(ids, jnp.int32)
        # owned token blocks cover the "state" leaves too: a slot's state
        # page IS its first table entry's block id, and both swap_out's
        # gather and swap_in's scatter walk ids in table order, so state
        # content rides along at position 0. Pinned xkv pages are NOT in
        # the owned set — gather them from the slot's xkv row.
        xidx = self._xkv_ids_j(slot)
        bufs = [np.asarray(jnp.take(l, xidx if kd == "xkv" else idx, axis=ax))  # repro: allow[host-sync] — swap-out IS the host transfer — gathering KV blocks is its job
                for l, ax, kd in zip(jax.tree.leaves(self._cache),
                                     self._pool_axes, self._kinds)]
        n_xkv = int(self._nbx) if self._nbx and self._xkv_tab[slot, 0] else 0
        self._free_slot_blocks(slot)
        self._live.discard(slot)
        self.swap_outs += 1
        self.swapped_blocks += len(ids) + n_xkv
        return {"bufs": bufs, "n_blocks": len(ids), "n_xkv": n_xkv,
                "pos": int(self._pos[slot]), "tok": int(self._tok[slot])}

    def swap_in(self, slot: int, handle: dict) -> None:
        """Readmit a swapped stream into ``slot`` (any free slot): claim
        fresh blocks, scatter the host buffers back, restore pos/token.
        The restored blocks are private copies — bit-identical content, so
        the decode trajectory is unchanged by the round trip."""
        if not self.paged:
            raise ValueError("swap_in requires a paged KV cache")
        self._ensure_rows(slot + 1)
        if slot in self._live:  # engine frees before reuse; be defensive
            self._free_slot_blocks(slot)
        n = int(handle["n_blocks"])
        nx = int(handle.get("n_xkv", 0))  # repro: allow[host-sync] — handle is host dict, not device data
        self._reserve(n + nx)
        ids = self._alloc.alloc(slot, n)
        if nx:
            self._xkv_tab[slot] = self._alloc.alloc_pinned(nx)
        idx = jnp.asarray(ids, jnp.int32)
        xidx = self._xkv_ids_j(slot)
        leaves, td = jax.tree.flatten(self._cache)
        out = []
        for l, b, ax, kd in zip(leaves, handle["bufs"], self._pool_axes,
                                self._kinds):
            tgt = xidx if kd == "xkv" else idx
            m = jnp.moveaxis(l, ax, 0).at[tgt].set(jnp.moveaxis(jnp.asarray(b), ax, 0))
            out.append(jnp.moveaxis(m, 0, ax))
        self._cache = jax.tree.unflatten(td, out)
        self._live.add(slot)
        self._pos[slot] = handle["pos"]
        self._tok[slot] = handle["tok"]
        self._pf_progress.pop(slot, None)
        self.swap_ins += 1

    # -- engine interface ----------------------------------------------------

    def start(self, slot: int, item: int) -> int:
        """Prefill ``item``'s prompt into ``slot``'s cache row (contiguous)
        or its freshly claimed pool blocks (paged); returns the first
        generated (greedy) token.

        With a prefix cache, cached blocks are SHARED into the slot's
        table instead of recomputed: a whole-prompt hit returns the cached
        first token with ZERO device work; a partial hit runs the same
        one-shot prefill jit but redirects the cached chunks' scatters to
        the trash block, so only the uncached tail blocks are written —
        either way the slot state is bit-identical to a private prefill."""
        self._check_admission_capacity()
        self._ensure_rows(slot + 1)
        toks = jnp.asarray(self.prompts[item][None, :])
        if self.paged:
            if slot in self._live:  # engine frees before reuse; be defensive
                self._free_slot_blocks(slot)
            S = self.prompts.shape[1]
            nb_pf = -(-S // self._bs_blk)
            shared, covered, first = ([], 0, None)
            if self._prefix is not None:
                shared, covered, first = self._prefix.lookup(self.prompts[item])
                if covered:
                    self._prefix.hits += 1
                    self._prefix.tokens_saved += covered
                    self.saved_blocks += len(shared)
            if shared:
                # share BEFORE reserving: the extra reference protects the
                # cached blocks from the eviction a reserve may trigger
                self._alloc.share(slot, shared)
            if first is not None:
                tok = int(first)  # whole prompt cached: TTFT ~ 0
            else:
                n_new = nb_pf - len(shared)
                try:
                    if n_new:
                        self._reserve(n_new)
                    blks = self._alloc.alloc(slot, n_new) if n_new else []
                    self._claim_xkv(slot)
                except PoolExhausted:
                    self._free_slot_blocks(slot)  # unwind the shares: retry-safe
                    raise
                ids = [0] * len(shared) + blks
                with tracing.span("runner.prefill", item):
                    self._cache, lab = self._prefill_fn_paged()(
                        self.params, self._cache, toks, jnp.asarray(ids, jnp.int32),
                        self._xkv_ids_j(slot),
                    )
                    tok = int(np.asarray(lab).reshape(-1)[0])  # repro: allow[host-sync] — sanctioned first-token read: admission needs the prefill label
            if self._prefix is not None:
                self._prefix.register(self.prompts[item], self._alloc.owned_ids(slot), tok)
        else:
            with tracing.span("runner.prefill", item):
                self._cache, lab = self._prefill_fn()(
                    self.params, self._cache, toks, jnp.int32(slot)
                )
                tok = int(np.asarray(lab).reshape(-1)[0])  # repro: allow[host-sync] — sanctioned first-token read: admission needs the prefill label
        self._live.add(slot)
        self._pos[slot] = self.prompts.shape[1]
        self._tok[slot] = tok
        self._pf_progress.pop(slot, None)  # one-shot start supersedes chunks
        return tok

    # -- chunked prefill (resumable against the same slot cache) ------------

    def prefill_begin(self, slot: int, item: int, n_tokens: int) -> Optional[int]:
        """First chunk of a chunked prefill: jitted prefill of the prompt's
        first ``n_tokens`` into the slot row (contiguous) or its freshly
        claimed pool blocks (paged). Returns the first generated token when
        ``n_tokens`` already covers the whole prompt (== ``start``), else
        None — resume with ``prefill_resume``; the slot cache is valid
        mid-prompt, so decode steps for OTHER slots interleave freely."""
        self._check_admission_capacity()
        S = self.prompts.shape[1]
        n = min(int(n_tokens), S)
        if n >= S:
            return self.start(slot, item)
        if n < 1:
            raise ValueError(f"prefill chunk must be >= 1 token, got {n_tokens}")
        self._ensure_rows(slot + 1)
        toks = jnp.asarray(self.prompts[item][None, :n])
        if self.paged:
            if slot in self._live:  # engine frees before reuse; be defensive
                self._free_slot_blocks(slot)
            shared, covered = [], 0
            if self._prefix is not None:
                # cached FULL chunks inside the first chunk are shared, not
                # recomputed (tail entries only apply to whole prompts)
                shared, covered, _ = self._prefix.lookup(self.prompts[item], limit=n)
                if covered:
                    self._prefix.hits += 1
                    self._prefix.tokens_saved += covered
                    self.saved_blocks += len(shared)
                if shared:
                    self._alloc.share(slot, shared)
                if covered == n:  # chunk fully cached: no device work
                    self._live.add(slot)
                    self._pos[slot] = n
                    self._pf_progress[slot] = item
                    return None
            n_new = -(-n // self._bs_blk) - len(shared)
            try:
                if self._prefix is not None:
                    self._reserve(n_new)
                blks = self._alloc.alloc(slot, n_new)
                self._claim_xkv(slot)
            except PoolExhausted:
                self._free_slot_blocks(slot)  # unwind the shares: retry-safe
                raise
            ids = [0] * len(shared) + blks
            self._cache, _ = self._prefill_fn_paged(n)(
                self.params, self._cache, toks, jnp.asarray(ids, jnp.int32),
                self._xkv_ids_j(slot),
            )
        else:
            self._cache, _ = self._prefill_fn()(
                self.params, self._cache, toks, jnp.int32(slot)
            )
        self._live.add(slot)
        self._pos[slot] = n
        self._pf_progress[slot] = item
        return None

    def prefill_resume(self, slot: int, n_tokens: int) -> Optional[int]:
        """Resume a chunked prefill: feed the next ``n_tokens`` prompt
        tokens through the no-ramp decode path, one token per dispatch —
        each token scatters its KV at the slot's position exactly as a
        decode step would (appending pool blocks as they fill on the paged
        layout), so the chunk is genuinely incremental against the shared
        slot cache. Returns the first generated token (the greedy
        continuation of the last prompt token) once the prompt is
        exhausted, else None. A production kernel would run the chunk as
        one (n_tokens)-wide dispatch; the per-token loop is the
        oracle-grade equivalent at the same cache layout."""
        if int(n_tokens) < 1:
            # silently feeding nothing would leave the slot stuck
            # mid-prefill with no progress signal — validate like
            # prefill_begin does
            raise ValueError(f"prefill chunk must be >= 1 token, got {n_tokens}")
        item = self._pf_progress[slot]
        S = self.prompts.shape[1]
        lab = None
        end = min(int(self._pos[slot]) + int(n_tokens), S)
        for p in range(int(self._pos[slot]), end):
            lab = self._feed_prompt_token(slot, int(self.prompts[item][p]))
        if int(self._pos[slot]) >= S:
            del self._pf_progress[slot]
            self._tok[slot] = int(lab)
            if self._prefix is not None:
                self._prefix.register(
                    self.prompts[item], self._alloc.owned_ids(slot), int(lab)
                )
            return int(lab)
        return None

    def _feed_prompt_token(self, slot: int, tok: int) -> int:
        """One resumed-prefill token through the (no-ramp) decode program:
        B=1 gather/scatter on the batched cache, per-row position — the
        same compiled path a decode step uses, so the cache layout cannot
        diverge between chunked and one-shot prefill."""
        rows = np.asarray([slot], np.int64)  # repro: allow[host-sync] — host row-index build — no device operand
        toks = jnp.asarray([[tok]], jnp.int32)
        pos = jnp.asarray(self._pos[rows], jnp.int32)
        if self.paged:
            self._claim_step_blocks([slot])
            tables = self._ship_tables(rows, 1, 1)
            self._cache, fl = self._decode_fn_paged_noramp()(
                self.params, self._cache, toks, pos, tables
            )
        else:
            self._cache, fl = self._decode_fn_noramp()(
                self.params, self._cache, toks, pos, jnp.asarray(rows, jnp.int32)
            )
        self.dispatches += 1
        self._pos[slot] += 1
        return int(np.asarray(fl).reshape(-1)[0])  # repro: allow[host-sync] — sanctioned token read: resumed prefill feeds it to the next chunk

    def _bucket_rows(self, B: int) -> int:
        """Bucket size for a step over ``B`` live slots. Subclasses with a
        data-parallel mesh raise the floor so the padded batch divides the
        `data` axis (both are powers of two)."""
        return _bucket(B)

    def _validate_active(self, active: Sequence[int]) -> List[int]:
        """Sorted active set, refusing (not silently truncating) oversize
        sets: truncation would return fewer record rows than the controller
        asked for and land rows against the wrong sites — the same fix
        ``ClassifierRunner.infer``/``LMTokenRunner.infer`` carry."""
        act = sorted(active)
        if len(act) > self.max_slots:
            raise ValueError(
                f"active ramp set has {len(act)} sites, max_slots={self.max_slots}"
            )
        return act

    def _validate_slots(self, slots: Sequence[int]) -> List[int]:
        slots = list(slots)
        for s in slots:
            if s not in self._live:
                raise KeyError(f"slot {s} is not live (freed or never started)")
            if s in self._pf_progress:
                raise KeyError(f"slot {s} is mid-prefill (resume its chunks first)")
        return slots

    def step(self, slots: Sequence[int], active: Sequence[int]):
        """ONE decode step — one jitted dispatch — for every slot in
        ``slots``. Returns (ramp_labels (K,B), ramp_unc (K,B), final (B,))
        with rows in sorted(active) order and columns in ``slots`` order."""
        slots = self._validate_slots(slots)
        act = self._validate_active(active)
        B = len(slots)
        if B == 0:  # nothing in flight: no dispatch (mirrors the loop runner)
            k = len(act)
            return (np.zeros((k, 0), np.int64), np.zeros((k, 0), np.float32),
                    np.zeros(0, np.int64))
        bucket = min(self._bucket_rows(B), self._rows)
        # pad with FREE rows (their state is garbage a future start()
        # overwrites wholesale), then with duplicates of stepped slots
        # (gather precedes every write, so duplicate indices scatter
        # identical values). NEVER a live-but-unstepped row: attention
        # writes would be idempotent previews, but an SSM mixer would
        # advance that slot's recurrent state off-schedule.
        free = [r for r in range(self._rows) if r not in self._live][: bucket - B]
        dup = [slots[i % B] for i in range(bucket - B - len(free))] if B else []
        rows = np.asarray(slots + free + dup, np.int64)  # repro: allow[host-sync] — host row-index build — no device operand
        toks = jnp.asarray(self._tok[rows].reshape(-1, 1), jnp.int32)
        pos = jnp.asarray(self._pos[rows], jnp.int32)
        k = len(act)
        if self.paged:
            # append a block only when a stepped slot's current block is
            # full (CoW-copying it first if it's shared); the claim totals
            # every stepped slot's needs and reserves them in ONE pass, so
            # a pool with no free block raises PoolExhausted here BEFORE
            # any allocator or device state changes
            self._claim_step_blocks(slots)
            # FREE pad rows keep stale table rows that may now reference
            # blocks owned by live slots — _ship_tables redirects them to
            # the reserved trash block 0 so their (discarded) scatters
            # land there
            tables_j = self._ship_tables(rows, B, B + len(free))
            if k:
                pad_act = jnp.asarray(act + [act[-1]] * (self.max_slots - k), jnp.int32)
                self._cache, (rl, ru, fl) = self._decode_fn_paged()(
                    self.params, self._cache, toks, pos, tables_j, pad_act
                )
        else:
            rows_j = jnp.asarray(rows, jnp.int32)
            if k:
                pad_act = jnp.asarray(act + [act[-1]] * (self.max_slots - k), jnp.int32)
                self._cache, (rl, ru, fl) = self._decode_fn()(
                    self.params, self._cache, toks, pos, rows_j, pad_act
                )
        if k:
            labels = np.asarray(rl).reshape(self.max_slots, -1)[:k, :B].astype(np.int64)  # repro: allow[host-sync] — sanctioned per-step record drain (the sync step_multi amortizes)
            unc = np.asarray(ru).reshape(self.max_slots, -1)[:k, :B].astype(np.float32)  # repro: allow[host-sync] — sanctioned per-step record drain (the sync step_multi amortizes)
        else:
            if self.paged:
                self._cache, fl = self._decode_fn_paged_noramp()(
                    self.params, self._cache, toks, pos, tables_j
                )
            else:
                self._cache, fl = self._decode_fn_noramp()(
                    self.params, self._cache, toks, pos, rows_j
                )
            labels = np.zeros((0, B), np.int64)
            unc = np.zeros((0, B), np.float32)
        self.dispatches += 1
        final = np.asarray(fl).reshape(-1)[:B].astype(np.int64)  # repro: allow[host-sync] — sanctioned per-step final-token drain (the sync step_multi amortizes)
        self._count_kv_pages(rows[:B], 1)
        self._pos[rows[:B]] += 1
        self._tok[rows[:B]] = final  # vanilla greedy trajectory (agreement baseline)
        return labels, unc, final

    def _count_kv_pages(self, rows: np.ndarray, nd: int) -> None:
        """Add ``nd`` executed steps of ``rows`` (before their positions
        advance) to ``kv_pages``: a row at ``pos`` attends to
        ``ceil((pos + 1) / bs)`` pages of its ``_max_blocks``-wide table."""
        if not self.paged:
            return
        pos = self._pos[rows][:, None] + np.arange(nd)
        self.kv_pages["attended"] += int((pos // self._bs_blk + 1).sum())
        self.kv_pages["table"] += len(rows) * nd * self._max_blocks

    def _thr_device(self, thr: np.ndarray):
        """Device-resident per-site exit thresholds, padded to
        ``max_slots`` with 0.0 (strict ``<`` means the pad sites can never
        fire). Re-pushed ONLY when the controller's values actually
        changed — unchanged windows reuse the device copy with zero
        host→device traffic."""
        pad = np.zeros(self.max_slots, np.float32)
        pad[: len(thr)] = thr
        if self._thr_host is None or not np.array_equal(pad, self._thr_host):
            self._thr_host = pad
            self._thr_dev = jnp.asarray(pad)
        return self._thr_dev

    def step_multi(self, slots: Sequence[int], active: Sequence[int],
                   n_steps: int, thresholds: np.ndarray):
        """A SYNC WINDOW: up to ``n_steps`` decode steps in ONE jitted
        dispatch (a ``lax.while_loop`` on device), with per-row exit
        decisions made ON DEVICE against ``thresholds`` — the device copy
        of the controller's per-active-site thresholds, deliberately
        STALE between syncs (the controller only retunes at window
        boundaries).

        Returns ``(labels, unc, finals, exits)`` with a leading
        executed-step axis ``nd <= n_steps``: ``labels``/``unc`` are
        ``(nd, K, B)`` in sorted(active) x ``slots`` order, ``finals``/
        ``exits`` are ``(nd, B)``. ``exits[t, b]`` is the FIRST active
        site whose on-device mask fired for slot ``b`` at window step
        ``t`` (−1 = none), bit-identical to ``simulate_exits`` over the
        returned records. The window terminates early after the first
        step where every live row exits — the remaining steps would be
        tokens the serving layer has already cut.

        Staleness/accuracy contract: exit decisions inside the window use
        the thresholds as of dispatch time, but the packed records stream
        back at the sync boundary and the controller REPLAYS every one of
        them — adaptation sees every token, delayed by at most one
        window, never lossy. ``n_steps=1`` is bit-identical to ``step``
        (the equivalence oracle the tests pin)."""
        with tracing.span("runner.prepare"):
            slots = self._validate_slots(slots)
            act = self._validate_active(active)
            k = len(act)
            if int(n_steps) < 1:
                raise ValueError(f"sync window needs n_steps >= 1, got {n_steps}")
            thr = np.asarray(thresholds, np.float32).reshape(-1)  # repro: allow[host-sync] — host threshold normalization — controller thresholds are host numpy
            if thr.shape[0] != k:
                raise ValueError(
                    f"thresholds has {thr.shape[0]} entries for {k} active sites"
                )
            B = len(slots)
            if B == 0:  # nothing in flight: no dispatch (mirrors ``step``)
                return (np.zeros((0, k, 0), np.int64), np.zeros((0, k, 0), np.float32),
                        np.zeros((0, 0), np.int64), np.zeros((0, 0), np.int64))
            headroom = min(self._cache_len - int(self._pos[s]) for s in slots)
            n = min(int(n_steps), max(1, headroom))
            n_max = _bucket(n)
            bucket = min(self._bucket_rows(B), self._rows)
            free = [r for r in range(self._rows) if r not in self._live][: bucket - B]
            dup = [slots[i % B] for i in range(bucket - B - len(free))]
            rows = np.asarray(slots + free + dup, np.int64)  # repro: allow[host-sync] — host row-index build — no device operand
            toks = jnp.asarray(self._tok[rows].reshape(-1, 1), jnp.int32)
            pos = jnp.asarray(self._pos[rows], jnp.int32)
            # FREE pad rows hold garbage — mask them out of the all-exited
            # early-termination vote (dup rows mirror a stepped slot, so
            # their vote is redundant either way)
            valid = np.zeros(bucket, bool)
            valid[:B] = True
            if self.paged:
                # pre-claim the whole window as n sequential per-step claims:
                # identical claim/eviction order to n ``step`` calls, so
                # block-id assignment off the min-heap stays bit-identical.
                # On PoolExhausted the appended tail is unwound to the
                # pre-window watermark (CoW copies stay — they are private,
                # content-identical replacements), leaving the claim
                # retry-safe for the engine's preempt-and-retry loop.
                al = self._alloc
                base_owned = {s: int(al.owned[s]) for s in slots}
                try:
                    for i in range(n):
                        self._claim_step_blocks(slots, offset=i)
                except PoolExhausted:
                    for s in slots:
                        al.release_tail(s, base_owned[s])
                    raise
                where = self._ship_tables(rows, B, B + len(free))
                fn = self._decode_multi_fn_paged if k else self._decode_multi_fn_paged_noramp
            else:
                where = jnp.asarray(rows, jnp.int32)
                fn = self._decode_multi_fn if k else self._decode_multi_fn_noramp
            if n < int(n_steps):  # counted once the window's claims hold
                self.short_windows["headroom"] += 1
            args = (toks, pos, where)
            if k:
                pad_act = jnp.asarray(act + [act[-1]] * (self.max_slots - k), jnp.int32)
                args += (pad_act, self._thr_device(thr))
            args += (jnp.int32(n), jnp.asarray(valid))
            fn = fn(n_max)
        with tracing.span("runner.dispatch"):
            self._cache, (rl, rm, fl, ex, ndv) = fn(self.params, self._cache, *args)
        self.dispatches += 1  # ONE dispatch per window, however many steps ran
        # the executed-step count is the ONE scalar the host must learn
        # before slicing the packed outputs — the single sync per window
        # is the whole point of the design
        with tracing.span("runner.wait"):
            nd = int(ndv)  # repro: allow[host-sync] — the one sanctioned sync per window
        with tracing.span("runner.drain"):
            # repro: allow[host-sync] — sync-boundary record drain (replay-completeness)
            labels = np.asarray(rl)[:nd, :k, :B].astype(np.int64)
            # host 1.0 − maxprob in f32 is the same IEEE op the per-step
            # program runs on device — unc stays bit-identical to ``step``
            # repro: allow[host-sync] — sync-boundary record drain (replay-completeness)
            unc = (np.float32(1.0) - np.asarray(rm)[:nd, :k, :B]).astype(np.float32)
            # repro: allow[host-sync] — sync-boundary record drain (replay-completeness)
            finals = np.asarray(fl)[:nd, :B].astype(np.int64)
            # repro: allow[host-sync] — sync-boundary exit-mask drain
            exits = np.asarray(ex)[:nd, :B].astype(np.int64)
            self._count_kv_pages(rows[:B], nd)
            self._pos[rows[:B]] += nd
            self._tok[rows[:B]] = finals[nd - 1]
            if nd < n:
                self.short_windows["early_end"] += 1
                if self.paged:
                    # early termination: return the blocks pre-claimed for
                    # steps that never ran. They were never written
                    # (executed-step writes all land within ``keep``), so
                    # releasing them cannot leak state; ``peak_blocks``
                    # keeps the transient high-water mark by design.
                    bs = self._bs_blk
                    for s in slots:
                        keep = max(base_owned[s], (int(self._pos[s]) - 1) // bs + 1)
                        self._alloc.release_tail(s, keep)
        return labels, unc, finals, exits

    def free(self, slot: int) -> None:
        if self.paged and self._alloc is not None and slot in self._live:
            self._free_slot_blocks(slot)
        self._live.discard(slot)
        self._pf_progress.pop(slot, None)


class ShardedDecodeRunner(DecodeRunner):
    """``DecodeRunner`` over a ``(data, model)`` device mesh: every jitted
    program is the tensor-parallel ``model.decode_sharded`` /
    ``decode_sharded_multi`` path (attention heads, FFN hidden, and —
    where the plan has MoE slots — experts sharded over `model`), with
    the KV cache (contiguous rows or the paged block pool) sharded by kv
    head so per-device KV bytes are ``total / tp``.

    Everything host-side is INHERITED unchanged: the one global
    ``BlockAllocator`` (page ids are mesh-global — only page *bytes*
    shard), block tables, prefix sharing/CoW/swap, claim ordering, bucket
    padding, the sync-window pre-claim/unwind. The TP decomposition is
    bitwise exact (see ``TpCtx`` in models.transformer), so records,
    tokens, and allocator state are bit-identical to the single-device
    ``DecodeRunner`` over any schedule — the property the fuzz harness
    pins at tp=2 and tp=4.

    Prefill runs REPLICATED inside the same shard_map: params enter
    sharded as decode holds them, each layer's shards are gathered just
    before the layer runs (``_prefill_params``), and each device slices
    its own kv-head block out of the freshly computed cache before
    scattering into its local shard — one dispatch per admit, and no
    device holds the whole model.

    ``dp > 1`` (contiguous caches only — a data-sharded paged pool would
    diverge the replicated pool copies) additionally shards decode rows
    over `data`; ``_bucket_rows`` raises the pad floor so every bucket
    divides the data axis.
    """

    def __init__(self, model, params, prompts, *, mesh=None, tp: int = 2,
                 dp: int = 1, **kw):
        from repro.compat import mesh_axis_size
        from repro.models import layers as _LY

        if mesh is None:
            devs = jax.devices()
            if len(devs) < dp * tp:
                raise ValueError(
                    f"mesh ({dp}x{tp}) needs {dp * tp} devices, "
                    f"have {len(devs)}"
                )
            mesh = jax.sharding.Mesh(
                np.asarray(devs[: dp * tp]).reshape(dp, tp), ("data", "model")
            )
        self.mesh = mesh
        self.tp = mesh_axis_size(mesh, "model")
        self.dp = mesh_axis_size(mesh, "data")
        self._maxes = _LY.TEST_AXES
        paged = str(getattr(model.cfg, "decode_attn", "")).startswith("paged")
        # fail at construction, not at the first step: the support matrix
        # carries the same why-note for the rejected cell
        model.tp_check(self.tp, dp=self.dp, paged=paged)
        super().__init__(model, params, prompts, **kw)

    # -- mesh plumbing -------------------------------------------------------

    def _bucket_rows(self, B: int) -> int:
        return max(_bucket(B), self.dp)

    def _ensure_rows(self, n: int) -> None:
        # a data-sharded step needs >= dp rows to gather from
        super()._ensure_rows(max(n, self.dp))

    def kv_stats(self) -> dict:
        out = super().kv_stats()
        out["tp"] = self.tp
        out["dp"] = self.dp
        if self._cache is not None:
            per_dev = {}
            for l in jax.tree.leaves(self._cache):
                if not hasattr(l, "addressable_shards"):
                    continue
                for sh in l.addressable_shards:
                    per_dev[sh.device.id] = (
                        per_dev.get(sh.device.id, 0)
                        + sh.data.size * np.dtype(l.dtype).itemsize
                    )
            if per_dev:
                out["per_device_cache_bytes"] = float(max(per_dev.values()))
        return out

    # -- jitted programs (shard_map variants) --------------------------------

    def _prefill_params(self):
        """Param specs and per-layer gather for prefill. Params enter
        sharded as decode holds them; each layer's shards are gathered
        just before it runs, so every device computes the whole prompt
        exactly as the single-device runner does (records stay
        bit-identical) while holding one layer's full weights at a time,
        never the whole model."""
        from jax.sharding import PartitionSpec as P

        pspecs = self.model.tp_param_specs(self._maxes)
        ax = self._maxes.model

        def gather_leaf(x, sp):
            if ax in tuple(sp):
                return jax.lax.all_gather(x, ax, axis=tuple(sp).index(ax), tiled=True)
            return x

        def gather(kind, i, p):
            sp = pspecs[kind][i]
            if kind == "blocks":  # one period's slice: drop the layer axis
                sp = jax.tree.map(lambda s: P(*tuple(s)[1:]), sp,
                                  is_leaf=lambda s: isinstance(s, P))
            return jax.tree.map(gather_leaf, p, sp,
                                is_leaf=lambda s: isinstance(s, P))

        return pspecs, gather

    def _local_heads(self, cache):
        """This device's kv-head block of a full prefill cache (the kv-head
        axis is ndim-2 of every leaf ``tp_check`` admits)."""
        mi = jax.lax.axis_index(self._maxes.model)
        return jax.tree.map(
            lambda x: jax.lax.dynamic_slice_in_dim(
                x, mi * (x.shape[x.ndim - 2] // self.tp),
                x.shape[x.ndim - 2] // self.tp, axis=x.ndim - 2),
            cache)

    def _prefill_fn(self):
        if self._pf is None:
            from jax.sharding import PartitionSpec as P

            from repro.compat import shard_map

            m, cache_len = self.model, self._cache_len
            mesh, axes = self.mesh, self._maxes
            pspecs, gather = self._prefill_params()
            runner = self

            def body(params, big, toks, slot):
                cache, outs = m.prefill(
                    params, toks, cache_len=cache_len, active_sites=None,
                    with_cache=True, moe_impl="dense", layer_params=gather,
                )
                big = runner._tree_put(big, runner._local_heads(cache), slot[None])
                lab = outs["final"]["label"]
                return big, (lab[:, 0] if lab.ndim == 2 else lab)

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def pf(params, big, toks, slot):
                cspecs = m.tp_cache_specs(big, axes)
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(pspecs, cspecs, P(), P()),
                    out_specs=(cspecs, P()), check_vma=False,
                )(params, big, toks, slot)

            self._pf = pf
        return self._pf

    def _prefill_fn_paged(self, n_tokens: Optional[int] = None):
        n_tokens = self.prompts.shape[1] if n_tokens is None else n_tokens
        if n_tokens not in self._pf_paged:
            from jax.sharding import PartitionSpec as P

            from repro.compat import shard_map

            m, cache_len = self.model, self._cache_len
            mesh, axes = self.mesh, self._maxes
            pspecs, gather = self._prefill_params()
            bs = self._bs_blk
            nb_pf = -(-n_tokens // bs)
            paxes = self._pool_axes

            def scatter(pool, cont, ax, blk_ids, nb):
                # identical to DecodeRunner's scatter, on the LOCAL kv-head
                # slice: every paged leaf the TP path admits is an attn k/v
                # with the kv-head axis at ndim-2 on both layouts
                x = jnp.moveaxis(cont, ax, 0)[0]
                t = jnp.moveaxis(x, ax, 0)
                need = nb * bs
                if t.shape[0] < need:
                    t = jnp.pad(t, [(0, need - t.shape[0])] + [(0, 0)] * (t.ndim - 1))
                t = t[:need].reshape((nb, bs) + t.shape[1:])
                p2 = jnp.moveaxis(pool, (ax, ax + 1), (0, 1))
                p2 = p2.at[blk_ids].set(t.astype(p2.dtype))
                return jnp.moveaxis(p2, (0, 1), (ax, ax + 1))

            def body(params, pools, toks, blk_ids, xkv_ids):
                cache, outs = m.prefill(
                    params, toks, cache_len=cache_len, active_sites=None,
                    with_cache=True, moe_impl="dense", layer_params=gather,
                )
                cache = self._local_heads(cache)
                leaves, td = jax.tree.flatten(pools)
                cl = jax.tree.leaves(cache)
                out = [
                    scatter(p, c, ax, blk_ids, nb_pf)
                    for p, c, ax in zip(leaves, cl, paxes)
                ]
                pools = jax.tree.unflatten(td, out)
                lab = outs["final"]["label"]
                return pools, (lab[:, 0] if lab.ndim == 2 else lab)

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def pf(params, pools, toks, blk_ids, xkv_ids):
                cspecs = m.tp_cache_specs(pools, axes)
                return shard_map(
                    body, mesh=mesh,
                    in_specs=(pspecs, cspecs, P(), P(), P()),
                    out_specs=(cspecs, P()), check_vma=False,
                )(params, pools, toks, blk_ids, xkv_ids)

            self._pf_paged[n_tokens] = pf
        return self._pf_paged[n_tokens]

    def _decode_fn(self):
        if self._dec is None:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec(params, big, toks, pos, rows, active):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode_sharded(
                    params, sub, toks, pos, mesh=mesh, axes=axes,
                    active_sites=active, moe_impl="dense",
                )
                big = self._tree_put(big, sub, rows)
                return big, (
                    outs["ramps"]["label"],
                    1.0 - outs["ramps"]["maxprob"],
                    outs["final"]["label"],
                )

            self._dec = dec
        return self._dec

    def _decode_fn_noramp(self):
        if self._dec0 is None:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec0(params, big, toks, pos, rows):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode_sharded(
                    params, sub, toks, pos, mesh=mesh, axes=axes,
                    active_sites=None, moe_impl="dense",
                )
                big = self._tree_put(big, sub, rows)
                return big, outs["final"]["label"]

            self._dec0 = dec0
        return self._dec0

    def _decode_fn_paged(self):
        if self._dec is None:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec(params, pools, toks, pos, tables, active):
                pools, outs = m.decode_sharded(
                    params, pools, toks, pos, mesh=mesh, axes=axes,
                    active_sites=active, moe_impl="dense", block_tables=tables,
                )
                return pools, (
                    outs["ramps"]["label"],
                    1.0 - outs["ramps"]["maxprob"],
                    outs["final"]["label"],
                )

            self._dec = dec
        return self._dec

    def _decode_fn_paged_noramp(self):
        if self._dec0 is None:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec0(params, pools, toks, pos, tables):
                pools, outs = m.decode_sharded(
                    params, pools, toks, pos, mesh=mesh, axes=axes,
                    active_sites=None, moe_impl="dense", block_tables=tables,
                )
                return pools, outs["final"]["label"]

            self._dec0 = dec0
        return self._dec0

    def _decode_multi_fn(self, n_max: int):
        if n_max not in self._decm:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm(params, big, toks, pos, rows, active, thr, n, valid):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode_sharded_multi(
                    params, sub, toks, pos, n, mesh=mesh, n_max=n_max,
                    axes=axes, active_sites=active, thresholds=thr,
                    row_valid=valid, moe_impl="dense",
                )
                big = self._tree_put(big, sub, rows)
                return big, outs

            self._decm[n_max] = decm
        return self._decm[n_max]

    def _decode_multi_fn_noramp(self, n_max: int):
        if n_max not in self._decm0:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm0(params, big, toks, pos, rows, n, valid):
                sub = self._tree_take(big, rows)
                sub, outs = m.decode_sharded_multi(
                    params, sub, toks, pos, n, mesh=mesh, n_max=n_max,
                    axes=axes, active_sites=None, row_valid=valid,
                    moe_impl="dense",
                )
                big = self._tree_put(big, sub, rows)
                return big, outs

            self._decm0[n_max] = decm0
        return self._decm0[n_max]

    def _decode_multi_fn_paged(self, n_max: int):
        if n_max not in self._decm:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm(params, pools, toks, pos, tables, active, thr, n, valid):
                pools, outs = m.decode_sharded_multi(
                    params, pools, toks, pos, n, mesh=mesh, n_max=n_max,
                    axes=axes, active_sites=active, thresholds=thr,
                    row_valid=valid, moe_impl="dense", block_tables=tables,
                )
                return pools, outs

            self._decm[n_max] = decm
        return self._decm[n_max]

    def _decode_multi_fn_paged_noramp(self, n_max: int):
        if n_max not in self._decm0:
            m, mesh, axes = self.model, self.mesh, self._maxes

            @partial(jax.jit, donate_argnums=1)  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def decm0(params, pools, toks, pos, tables, n, valid):
                pools, outs = m.decode_sharded_multi(
                    params, pools, toks, pos, n, mesh=mesh, n_max=n_max,
                    axes=axes, active_sites=None, row_valid=valid,
                    moe_impl="dense", block_tables=tables,
                )
                return pools, outs

            self._decm0[n_max] = decm0
        return self._decm0[n_max]


class LoopDecodeRunner:
    """Per-slot-loop reference runner: the pre-batched implementation kept
    for the batched-vs-loop equivalence tests and the dispatch-count
    benchmark. Slots are independent B=1 caches; every engine step issues
    one jitted ``model.decode`` PER SLOT (B dispatches + B small cache
    trees per step — the serialized hot path ``DecodeRunner`` replaces)."""

    def __init__(self, model, params, prompts: np.ndarray, *, max_new_tokens: int = 64,
                 max_slots: int = 8):
        self.model = model
        self.params = params
        self.prompts = np.asarray(prompts, np.int32)  # (N, S)
        self.max_new = max_new_tokens
        self.max_slots = max_slots
        self.n_sites = len(model.sites)
        self.dispatches = 0  # jitted decode calls (B per step)
        self._slots = {}
        self._pf = None
        self._dec = None
        self._dec0 = None  # no-ramp (vanilla) decode variant

    def _prefill_fn(self):
        if self._pf is None:
            m, S = self.model, self.prompts.shape[1]
            cache_len = S + self.max_new

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def pf(params, toks):
                cache, outs = m.prefill(
                    params, toks, cache_len=cache_len, active_sites=None,
                    with_cache=True, moe_impl="dense",
                )
                lab = outs["final"]["label"]
                return cache, (lab[:, 0] if lab.ndim == 2 else lab)

            self._pf = pf
        return self._pf

    def _decode_fn(self):
        if self._dec is None:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec(params, cache, tok, pos, active):
                new_cache, outs = m.decode(
                    params, cache, tok, pos, active_sites=active, moe_impl="dense"
                )
                return new_cache, (
                    outs["ramps"]["label"],
                    1.0 - outs["ramps"]["maxprob"],
                    outs["final"]["label"],
                )

            self._dec = dec
        return self._dec

    def _decode_fn_noramp(self):
        if self._dec0 is None:
            m = self.model

            @jax.jit  # repro: allow[jit-cache-hygiene] — wrapper memoized by the enclosing runner
            def dec0(params, cache, tok, pos):
                new_cache, outs = m.decode(
                    params, cache, tok, pos, active_sites=None, moe_impl="dense"
                )
                return new_cache, outs["final"]["label"]

            self._dec0 = dec0
        return self._dec0

    def start(self, slot: int, item: int) -> int:
        toks = jnp.asarray(self.prompts[item][None, :])
        cache, lab = self._prefill_fn()(self.params, toks)
        tok = int(np.asarray(lab).reshape(-1)[0])  # repro: allow[host-sync] — sanctioned first-token read (per-slot loop oracle)
        self._slots[slot] = {"cache": cache, "pos": self.prompts.shape[1], "tok": tok}
        return tok

    def step(self, slots: Sequence[int], active: Sequence[int]):
        """One decode step for every slot in ``slots`` — one jitted B=1
        dispatch per slot. Row/column order matches ``DecodeRunner.step``."""
        act = sorted(active)
        if len(act) > self.max_slots:
            # refuse, never silently truncate (matches DecodeRunner.step)
            raise ValueError(
                f"active ramp set has {len(act)} sites, max_slots={self.max_slots}"
            )
        k = len(act)
        labels = np.zeros((max(k, 1), len(slots)), np.int64)
        unc = np.full((max(k, 1), len(slots)), 1.0, np.float32)
        final = np.zeros(len(slots), np.int64)
        if k:
            pad_act = jnp.asarray(act + [act[-1]] * (self.max_slots - k), jnp.int32)
            dec = self._decode_fn()
        else:
            dec0 = self._decode_fn_noramp()
        for b, s in enumerate(slots):
            st = self._slots[s]
            tok = jnp.asarray([[st["tok"]]], jnp.int32)
            if k:
                st["cache"], (rl, ru, fl) = dec(
                    self.params, st["cache"], tok, jnp.int32(st["pos"]), pad_act
                )
                labels[:, b] = np.asarray(rl).reshape(self.max_slots, -1)[:k, 0]  # repro: allow[host-sync] — sanctioned record drain (per-slot loop oracle)
                unc[:, b] = np.asarray(ru).reshape(self.max_slots, -1)[:k, 0]  # repro: allow[host-sync] — sanctioned record drain (per-slot loop oracle)
            else:
                st["cache"], fl = dec0(self.params, st["cache"], tok, jnp.int32(st["pos"]))
            self.dispatches += 1
            fl = int(np.asarray(fl).reshape(-1)[0])  # repro: allow[host-sync] — sanctioned token read (per-slot loop oracle)
            final[b] = fl
            st["pos"] += 1
            st["tok"] = fl  # vanilla greedy trajectory (agreement baseline)
        if k == 0:
            return labels[:0], unc[:0], final
        return labels[:k], unc[:k], final

    def free(self, slot: int) -> None:
        self._slots.pop(slot, None)


class SyntheticDecodeRunner:
    """Profile-only generative runner — the decode analogue of
    ``SyntheticRunner``: deterministic per-token ramp records without a
    model. A fixed fraction of tokens is "easy" (confidently predictable
    from ``exit_site`` onward, ramp label agreeing with the final token);
    the rest stay uncertain and disagreeing at every ramp, so an
    over-opened threshold costs accuracy exactly as with a trained LM.
    Used by the generative benchmarks/sweeps where training an LM per
    configuration would dominate runtime."""

    def __init__(self, n_sites: int, exit_site: int, easy_frac: float = 0.7,
                 vocab: int = 101):
        self.n_sites = n_sites
        self.exit_site = exit_site
        self.easy_frac = easy_frac
        self.vocab = vocab
        self._slots = {}

    def _token(self, item: int, t: int) -> int:
        return (item * 31 + t * 7 + 3) % self.vocab

    def _easy(self, item: int, t: int) -> bool:
        return ((item * 131 + t * 17) % 100) < self.easy_frac * 100

    def start(self, slot: int, item: int) -> int:
        self._slots[slot] = {"item": item, "t": 0}
        return self._token(item, 0)

    def step(self, slots: Sequence[int], active: Sequence[int]):
        act = sorted(active)
        k = len(act)
        B = len(slots)
        labels = np.zeros((max(k, 1), B), np.int64)
        unc = np.full((max(k, 1), B), 0.9, np.float32)
        final = np.zeros(B, np.int64)
        for b, s in enumerate(slots):
            st = self._slots[s]
            st["t"] += 1
            item, t = st["item"], st["t"]
            fin = self._token(item, t)
            final[b] = fin
            easy = self._easy(item, t)
            for j, site in enumerate(act):
                if easy and site >= self.exit_site:
                    labels[j, b] = fin
                    unc[j, b] = 0.02
                else:
                    labels[j, b] = (fin + 1) % self.vocab
                    unc[j, b] = 0.9
        if k == 0:
            return labels[:0], unc[:0], final
        return labels[:k], unc[:k], final

    def free(self, slot: int) -> None:
        self._slots.pop(slot, None)
