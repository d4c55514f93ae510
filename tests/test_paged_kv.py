"""Paged KV cache: block-allocator properties, paged-kernel correctness,
pool exhaustion, and memory scaling.

Allocator property tests draw hundreds of random alloc/free schedules
from a module-seeded generator (suite policy: no hypothesis) and check
the three invariants the paged runner's soundness rests on: disjoint
ownership, pool conservation, and atomic failure at exhaustion. Kernel
tests validate the Pallas block-table walk against the jnp gather oracle
(interpret mode on CPU; the full shape sweep is ``-m slow``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import BlockAllocator, PoolExhausted

RNG = np.random.default_rng(0xB10C)


# ---------------------------------------------------------------------------
# allocator properties


def _check_invariants(al: BlockAllocator):
    owned = []
    for s in range(al.table.shape[0]):
        ids = al.owned_ids(s)
        assert all(1 <= b <= al.n_blocks for b in ids), "invalid block id"
        owned.extend(ids)
    assert len(owned) == len(set(owned)), "a block is owned by two slots"
    assert al.n_free + len(owned) == al.n_blocks, "pool not conserved"


@pytest.mark.parametrize("trial", range(8))
def test_allocator_random_schedules(trial):
    """Hundreds of random alloc/free ops (~40 schedules x 8 trials):
    no double ownership, pool conserved, table mirrors a reference model."""
    for _ in range(40):
        n_slots = int(RNG.integers(2, 6))
        max_blocks = int(RNG.integers(2, 6))
        n_blocks = int(RNG.integers(1, n_slots * max_blocks + 2))
        al = BlockAllocator(n_blocks, max_blocks, n_slots)
        ref = {s: [] for s in range(n_slots)}  # reference ownership model
        for _ in range(int(RNG.integers(5, 25))):
            s = int(RNG.integers(n_slots))
            if RNG.random() < 0.65:
                n = int(RNG.integers(1, max_blocks + 1))
                try:
                    ids = al.alloc(s, n)
                except PoolExhausted:
                    assert al.n_free < n
                except ValueError:
                    assert len(ref[s]) + n > max_blocks
                else:
                    assert len(ids) == n
                    ref[s].extend(ids)
            else:
                al.free_slot(s)
                ref[s] = []
            _check_invariants(al)
            for t in range(n_slots):
                assert al.owned_ids(t) == ref[t]
        for s in range(n_slots):
            al.free_slot(s)
        assert al.n_free == al.n_blocks and al.live_blocks == 0


def _check_share_invariants(al: BlockAllocator, ref, pinned):
    """The refcount invariants from the BlockAllocator docstring, checked
    against an independent reference model (ref: slot -> id list in table
    order, possibly with repeats across slots; pinned: pin-id multiset)."""
    import collections

    for s, ids in ref.items():
        assert al.owned_ids(s) == ids, "table diverged from reference model"
    refc = collections.Counter(b for ids in ref.values() for b in ids)
    refc.update(pinned)
    for b, c in refc.items():
        assert 1 <= b <= al.n_blocks, "reference to invalid block id"
    np.testing.assert_array_equal(
        al.refcount[1:], [refc.get(b, 0) for b in range(1, al.n_blocks + 1)]
    )
    assert al.pins == len(pinned)
    assert int(al.refcount.sum()) == int(al.owned.sum()) + al.pins
    free = set(al._free)
    assert len(free) == al.n_free, "duplicate id on the free heap"
    assert all(al.refcount[b] == 0 for b in free), "block both free and referenced"
    assert al.n_free + int((al.refcount > 0).sum()) == al.n_blocks, "pool leak"


@pytest.mark.parametrize("trial", range(8))
def test_allocator_sharing_random_schedules(trial):
    """Sharing-era property fuzz (~40 schedules x 8 trials x ~25 ops):
    random alloc/share/cow/pin/unpin/free schedules against a reference
    model. After every op: refcounts equal the reference multiset,
    refcount.sum() == owned.sum() + pins, the free heap is disjoint from
    referenced blocks, and nothing leaks. Failed ops (exhaustion, cap)
    must leave all of that untouched — the model is not updated on
    failure, so any partial mutation trips the next check."""
    for _ in range(40):
        n_slots = int(RNG.integers(2, 6))
        max_blocks = int(RNG.integers(2, 6))
        n_blocks = int(RNG.integers(2, n_slots * max_blocks + 2))
        al = BlockAllocator(n_blocks, max_blocks, n_slots)
        ref = {s: [] for s in range(n_slots)}
        pinned = []
        for _ in range(int(RNG.integers(10, 30))):
            s = int(RNG.integers(n_slots))
            live = sorted({b for ids in ref.values() for b in ids} | set(pinned))
            op = RNG.choice(["alloc", "share", "cow", "pin", "unpin", "free"])
            if op == "alloc":
                n = int(RNG.integers(1, max_blocks + 1))
                try:
                    ids = al.alloc(s, n)
                except PoolExhausted:
                    assert al.n_free < n
                except ValueError:
                    assert len(ref[s]) + n > max_blocks
                else:
                    ref[s].extend(ids)
            elif op == "share" and live:
                k = int(RNG.integers(1, min(len(live), max_blocks) + 1))
                ids = [int(b) for b in RNG.choice(live, k, replace=False)]
                try:
                    al.share(s, ids)
                except ValueError:
                    assert len(ref[s]) + k > max_blocks
                else:
                    ref[s].extend(ids)
            elif op == "cow" and ref[s]:
                idx = int(RNG.integers(len(ref[s])))
                try:
                    old, new = al.cow(s, idx)
                except PoolExhausted:
                    assert al.n_free < 1
                else:
                    assert old == ref[s][idx] and new != old
                    ref[s][idx] = new
            elif op == "pin" and live:
                b = int(RNG.choice(live))
                al.pin(b)
                pinned.append(b)
            elif op == "unpin" and pinned:
                b = pinned.pop(int(RNG.integers(len(pinned))))
                al.unpin(b)
            elif op == "free":
                al.free_slot(s)
                ref[s] = []
            _check_share_invariants(al, ref, pinned)
        for s in range(n_slots):
            al.free_slot(s)
        for b in pinned:
            al.unpin(b)
        assert al.n_free == al.n_blocks and al.pins == 0
        assert (al.refcount == 0).all()


def test_allocator_share_and_cow_refcounts():
    """Deterministic walk of the sharing lifecycle: share bumps refcount,
    cow gives the writer a private block (old keeps its other holders),
    and a shared block outlives the slot that allocated it."""
    al = BlockAllocator(6, max_blocks_per_slot=3, n_slots=3)
    ids = al.alloc(0, 2)  # [1, 2]
    al.share(1, ids)
    assert al.owned_ids(1) == ids and al.refcount[1] == al.refcount[2] == 2
    assert al.live_blocks == 2  # shared, not duplicated
    old, new = al.cow(1, 0)
    assert (old, new) == (1, 3)
    assert al.owned_ids(1) == [3, 2] and al.owned_ids(0) == [1, 2]
    assert al.refcount[1] == 1 and al.refcount[3] == 1 and al.refcount[2] == 2
    al.free_slot(0)  # block 2 survives via slot 1's reference
    assert al.refcount[2] == 1 and al.owned_ids(1) == [3, 2]
    al.free_slot(1)
    assert al.n_free == al.n_blocks


def test_allocator_pin_keeps_block_alive():
    al = BlockAllocator(3, max_blocks_per_slot=2, n_slots=2)
    (b,) = al.alloc(0, 1)
    al.pin(b)
    al.free_slot(0)
    assert al.refcount[b] == 1 and al.live_blocks == 1  # cache ref holds it
    al.unpin(b)
    assert al.live_blocks == 0
    assert al.alloc(1, 1) == [b]  # recycled
    # dead / reserved / out-of-range blocks cannot be shared or pinned
    with pytest.raises(ValueError):
        al.share(0, [3])
    with pytest.raises(ValueError):
        al.pin(0)


def test_allocator_exhaustion_is_atomic():
    """A failing multi-block alloc must not mutate the table or free list."""
    al = BlockAllocator(4, max_blocks_per_slot=6, n_slots=2)
    al.alloc(0, 3)
    before = (al.table.copy(), al.owned.copy(), al.n_free)
    with pytest.raises(PoolExhausted):
        al.alloc(1, 2)  # only 1 free
    np.testing.assert_array_equal(al.table, before[0])
    np.testing.assert_array_equal(al.owned, before[1])
    assert al.n_free == before[2]
    # the survivor block is still allocatable after the failure
    assert al.alloc(1, 1) == [4]


def test_allocator_free_returns_every_block():
    al = BlockAllocator(6, max_blocks_per_slot=3, n_slots=3)
    for s in range(3):
        al.alloc(s, 2)
    assert al.n_free == 0 and al.peak_blocks == 6
    for s in range(3):
        al.free_slot(s)
    assert al.n_free == 6
    # freed ids recycle deterministically lowest-first
    assert al.alloc(1, 2) == [1, 2]
    # stale table entries of freed slots stay valid (trash) pool indices
    assert (al.table[0] == 0).all() and (al.table[2] == 0).all()


def test_allocator_grow():
    al = BlockAllocator(2, max_blocks_per_slot=2, n_slots=1)
    al.alloc(0, 2)
    al.grow_slots(3)
    al.grow_pool(5)
    assert al.table.shape[0] == 3 and al.n_free == 3
    assert al.alloc(1, 2) == [3, 4]
    _check_invariants(al)


# ---------------------------------------------------------------------------
# paged kernel vs oracles


def _rand_paged(rng, B, H, KH, hd, bs, nb, dtype=np.float32):
    P = B * nb + 1
    q = jnp.asarray(rng.standard_normal((B, H, hd)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, bs, KH, hd)), dtype)
    vp = jnp.asarray(rng.standard_normal((P, bs, KH, hd)), dtype)
    # rows own disjoint random blocks (ids >= 1; 0 is the trash block)
    ids = rng.permutation(np.arange(1, P))[: B * nb].reshape(B, nb)
    table = jnp.asarray(ids, jnp.int32)
    pos = jnp.asarray(rng.integers(0, nb * bs, B), jnp.int32)
    return q, kp, vp, table, pos


def test_paged_ref_matches_contiguous_gather():
    """The paged oracle IS the contiguous oracle on the gathered layout —
    bit-identical, which is what the runner equivalence harness rests on."""
    from repro.kernels.decode_attention import (
        decode_attention_ref,
        paged_decode_attention_ref,
    )

    rng = np.random.default_rng(0)
    B, H, KH, hd, bs, nb = 3, 4, 2, 8, 4, 4
    q, kp, vp, table, pos = _rand_paged(rng, B, H, KH, hd, bs, nb)
    o = paged_decode_attention_ref(q, kp, vp, table, pos)
    k = kp[table].reshape(B, nb * bs, KH, hd).transpose(0, 2, 1, 3)
    v = vp[table].reshape(B, nb * bs, KH, hd).transpose(0, 2, 1, 3)
    np.testing.assert_array_equal(
        np.asarray(o), np.asarray(decode_attention_ref(q, k, v, pos))
    )


def test_paged_kernel_matches_ref():
    from repro.kernels.decode_attention import (
        paged_decode_attention,
        paged_decode_attention_ref,
    )

    rng = np.random.default_rng(1)
    B, H, KH, hd, bs, nb = 2, 4, 2, 16, 8, 3
    q, kp, vp, table, pos = _rand_paged(rng, B, H, KH, hd, bs, nb)
    o_k = paged_decode_attention(q, kp, vp, table, pos, interpret=True)
    o_r = paged_decode_attention_ref(q, kp, vp, table, pos)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-6, rtol=2e-6)


def test_paged_kernel_refuses_part_lane_heads():
    """A head size that does not fill whole 128-lane tiles cannot be
    sliced out of the pool a page at a time on the chip: the kernel
    refuses it and ``attend_decode_paged`` gives such pools the gathered
    reference."""
    from repro.kernels.decode_attention import (
        attend_decode_paged,
        paged_decode_attention,
        paged_decode_attention_ref,
    )

    rng = np.random.default_rng(2)
    q, kp, vp, table, pos = _rand_paged(rng, 2, 4, 2, 16, 8, 3)
    with pytest.raises(ValueError, match="multiple of 128"):
        paged_decode_attention(q, kp, vp, table, pos)
    np.testing.assert_array_equal(
        np.asarray(attend_decode_paged(q, kp, vp, table, pos, use_kernel=True)),
        np.asarray(paged_decode_attention_ref(q, kp, vp, table, pos)))


@pytest.mark.parametrize(
    "B,H,KH,hd,bs,nb,pos,idle,trash",
    [
        # single block: init tile is also the final tile
        pytest.param(1, 2, 2, 8, 4, 1, None, 0, "finite", marks=pytest.mark.slow),
        pytest.param(2, 4, 2, 16, 8, 3, None, 0, "finite", marks=pytest.mark.slow),
        # GQA 4:1
        pytest.param(3, 8, 2, 32, 16, 2, None, 0, "finite", marks=pytest.mark.slow),
        # MHA, many small blocks
        pytest.param(4, 4, 4, 16, 4, 5, None, 0, "finite", marks=pytest.mark.slow),
        # 2:1 grouping
        pytest.param(2, 6, 3, 16, 8, 4, None, 0, "finite", marks=pytest.mark.slow),
        # compute blocks of 16 pages at bs 16: pos 0, the last token of a
        # page, the last of a compute block and the first of the next
        pytest.param(4, 4, 2, 16, 16, 32, [0, 15, 255, 256], 0, "finite",
                     id="two-compute-blocks"),
        # 21 pages: the last compute block holds 5; the first token of a
        # page, the table's last token, and a row that skips the last block
        pytest.param(3, 6, 2, 16, 16, 21, [16, 335, 200], 0, "finite", id="ragged-last-block"),
        # bucket-padding rows as the runner builds them: every table entry
        # on the trash block, at a stale position (0, and one past the table)
        pytest.param(3, 4, 2, 16, 16, 21, [40, 0, 336], 2, "finite", id="idle-rows"),
        # dead table entries point at a trash block full of NaN: never read
        pytest.param(3, 4, 2, 16, 16, 21, [0, 257, 335], 0, "nan", id="nan-trash"),
    ],
)
def test_paged_kernel_sweep(B, H, KH, hd, bs, nb, pos, idle, trash):
    """Interpret-mode Pallas sweep over head groupings / block geometries,
    including per-row positions at every in-block offset. Dead table
    entries point at the trash block 0, as the allocator keeps them."""
    from repro.kernels.decode_attention import (
        paged_decode_attention,
        paged_decode_attention_ref,
    )

    rng = np.random.default_rng(B * 1000 + nb)
    q, kp, vp, table, _ = _rand_paged(rng, B, H, KH, hd, bs, nb)
    if pos is None:
        # force the full offset range across rows: first/last token of a block
        pos = [(i * bs + [0, bs - 1, bs // 2][i % 3]) % (nb * bs) for i in range(B)]
    pos = jnp.asarray(pos, jnp.int32)
    live = np.minimum(np.asarray(pos) // bs + 1, nb)
    table = jnp.where(jnp.arange(nb)[None] < live[:, None], table, 0)
    if idle:
        table = table.at[B - idle:].set(0)
    kp_r, vp_r = kp, vp
    if trash == "nan":
        kp, vp = kp.at[0].set(jnp.nan), vp.at[0].set(jnp.nan)
        kp_r, vp_r = kp.at[0].set(0.0), vp.at[0].set(0.0)
    o_k = paged_decode_attention(q, kp, vp, table, pos, interpret=True)
    o_r = paged_decode_attention_ref(q, kp_r, vp_r, table, pos)
    assert np.isfinite(np.asarray(o_k)).all()
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-6, rtol=2e-6)


def _rand_paged_mla(rng, B, H, r, dr, bs, nb, dtype=jnp.float32):
    P = B * nb + 4
    q_lat = jnp.asarray(rng.standard_normal((B, H, r)), dtype)
    q_pe = jnp.asarray(rng.standard_normal((B, H, dr)), dtype)
    cp = jnp.asarray(rng.standard_normal((P, bs, r)), dtype)
    kp = jnp.asarray(rng.standard_normal((P, bs, dr)), dtype)
    ids = rng.permutation(np.arange(1, P))[: B * nb].reshape(B, nb)
    table = jnp.asarray(ids, jnp.int32)
    pos = jnp.asarray(rng.integers(0, nb * bs, B), jnp.int32)
    return q_lat, q_pe, cp, kp, table, pos


def test_paged_mla_ref_matches_contiguous_math():
    """The paged MLA oracle IS the absorbed contiguous math on the
    gathered latent layout — bit-identical, which is what the MLA runner
    equivalence rests on (``decode_attn='paged'`` routes here)."""
    from repro.kernels.decode_attention import paged_mla_decode_attention_ref

    rng = np.random.default_rng(2)
    B, H, r, dr, bs, nb = 3, 4, 16, 8, 4, 4
    scale = 1.0 / np.sqrt(r + dr)
    ql, qp, cp, kp, table, pos = _rand_paged_mla(rng, B, H, r, dr, bs, nb)
    o = paged_mla_decode_attention_ref(ql, qp, cp, kp, table, pos, scale=scale)
    c = cp[table].reshape(B, nb * bs, r)
    k = kp[table].reshape(B, nb * bs, dr)
    s = (
        jnp.einsum("bhr,bsr->bhs", ql, c) + jnp.einsum("bhn,bsn->bhs", qp, k)
    ) * scale
    mask = jnp.arange(nb * bs)[None, None] <= pos[:, None, None]
    probs = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    np.testing.assert_array_equal(
        np.asarray(o), np.asarray(jnp.einsum("bhs,bsr->bhr", probs, c))
    )


def test_paged_mla_kernel_matches_ref():
    """Kernel pairing for the paged MLA Pallas kernel: interpret-mode
    output vs the jnp oracle, per-row positions at mixed block offsets."""
    from repro.kernels.decode_attention import (
        paged_mla_decode_attention,
        paged_mla_decode_attention_ref,
    )

    rng = np.random.default_rng(3)
    B, H, r, dr, bs, nb = 2, 4, 16, 8, 8, 3
    scale = 1.0 / np.sqrt(r + dr)
    ql, qp, cp, kp, table, pos = _rand_paged_mla(rng, B, H, r, dr, bs, nb)
    pos = jnp.asarray([0, nb * bs - 1], jnp.int32)  # first + last offsets
    o_k = paged_mla_decode_attention(ql, qp, cp, kp, table, pos,
                                     scale=scale, interpret=True)
    o_r = paged_mla_decode_attention_ref(ql, qp, cp, kp, table, pos, scale=scale)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-6, rtol=2e-6)


@pytest.mark.slow
@pytest.mark.parametrize(
    "B,H,r,dr,bs,nb",
    [
        (1, 2, 8, 4, 4, 1),  # single block: init tile is also the final tile
        (3, 4, 16, 8, 4, 4),
        (2, 8, 32, 16, 8, 3),
    ],
)
def test_paged_mla_kernel_sweep(B, H, r, dr, bs, nb):
    from repro.kernels.decode_attention import (
        paged_mla_decode_attention,
        paged_mla_decode_attention_ref,
    )

    rng = np.random.default_rng(B * 100 + nb)
    scale = 1.0 / np.sqrt(r + dr)
    ql, qp, cp, kp, table, pos = _rand_paged_mla(rng, B, H, r, dr, bs, nb)
    o_k = paged_mla_decode_attention(ql, qp, cp, kp, table, pos,
                                     scale=scale, interpret=True)
    o_r = paged_mla_decode_attention_ref(ql, qp, cp, kp, table, pos, scale=scale)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-6, rtol=2e-6)


# ---------------------------------------------------------------------------
# paged runner: exhaustion + memory scaling


@pytest.fixture(scope="module")
def paged_setup():
    from repro.configs import get_tiny
    from repro.models import build_model

    cfg = get_tiny("qwen2-1.5b").replace(n_layers=2, vocab_size=128, decode_attn="paged")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    prompts = np.random.default_rng(1).integers(0, 128, (8, 8)).astype(np.int32)
    return cfg, model, params, prompts


def test_runner_pool_exhaustion_raises_cleanly(paged_setup):
    from repro.serving import DecodeRunner

    _, model, params, prompts = paged_setup
    # prompt takes 2 blocks of 4; pool of 5 blocks fits two prompts + one
    # appended block, then runs dry
    r = DecodeRunner(model, params, prompts, max_new_tokens=8, max_slots=2,
                     n_slots=4, kv_block_size=4, kv_blocks=5)
    r.start(0, 0)
    r.start(1, 1)
    assert r._alloc.live_blocks == 4
    with pytest.raises(PoolExhausted):
        r.start(2, 2)  # needs 2 blocks, 1 free
    # the prompt exactly fills 2 blocks, so the first decode step must
    # append one block per slot — only one is free; the step raises
    # BEFORE any device update, leaving the allocator consistent
    with pytest.raises(PoolExhausted):
        r.step([0, 1], [0])
    assert r._alloc.n_free + r._alloc.live_blocks == r._alloc.n_blocks
    # freeing a slot returns its blocks; the survivor appends and proceeds
    r.free(0)
    assert r._alloc.n_free >= 2
    r.step([1], [0])
    assert r._pos[1] == 9


def test_step_block_claim_is_all_or_nothing(paged_setup):
    """Regression: a multi-slot step that exhausts the pool on a LATER
    slot's append must not have claimed blocks for earlier slots. The old
    per-slot append loop allocated slot 0's block before discovering slot
    1 couldn't get one — the retried step then double-appended. The claim
    is now precomputed for the whole batch and reserved atomically."""
    from repro.serving import DecodeRunner

    _, model, params, prompts = paged_setup
    # prompt = 8 tokens = 2 blocks of 4; pool of 5: two started slots own
    # 4 blocks, and the first decode step needs one append PER slot
    r = DecodeRunner(model, params, prompts, max_new_tokens=8, max_slots=2,
                     n_slots=4, kv_block_size=4, kv_blocks=5)
    r.start(0, 0)
    r.start(1, 1)
    al = r._alloc
    before = (al.table.copy(), al.owned.copy(), al.n_free, np.asarray(r._pos).copy())
    with pytest.raises(PoolExhausted):
        r.step([0, 1], [0])  # needs 2 appends, 1 free
    np.testing.assert_array_equal(al.table, before[0])  # slot 0 untouched too
    np.testing.assert_array_equal(al.owned, before[1])
    assert al.n_free == before[2]
    np.testing.assert_array_equal(np.asarray(r._pos), before[3])
    # after the failed step the survivor path still works untainted
    r.free(0)
    r.step([1], [0])
    assert int(np.asarray(r._pos)[1]) == 9


def test_paged_memory_scales_with_live_tokens(paged_setup):
    """The acceptance claim at unit scale: with few live tokens the paged
    pool is far smaller than n_slots * max_len contiguous rows, while
    records stay bit-identical to the contiguous runner."""
    from repro.models import build_model
    from repro.serving import DecodeRunner

    cfg, model, params, prompts = paged_setup
    n_slots = 8
    kw = dict(max_new_tokens=8, max_slots=2, n_slots=n_slots)
    cont = DecodeRunner(
        build_model(cfg.replace(decode_attn="ref")), params, prompts, **kw
    )
    # 2 concurrent short requests -> 2 slots * 4 blocks; pool of 8 blocks
    paged = DecodeRunner(model, params, prompts, kv_block_size=4, kv_blocks=8, **kw)
    for r in (cont, paged):
        r.start(0, 0)
        r.start(5, 3)
    for _ in range(4):
        lc, uc, fc = cont.step([0, 5], [0])
        lp, up, fp = paged.step([0, 5], [0])
        np.testing.assert_array_equal(lp, lc)
        np.testing.assert_array_equal(up, uc)
        np.testing.assert_array_equal(fp, fc)
    # contiguous holds n_slots(8) * cache_len(16) token rows; the paged
    # pool holds (kv_blocks + trash)(9) * 4 = 36 token slots
    assert cont.cache_bytes() == paged.cache_bytes() * (8 * 16) // 36
    assert paged.cache_bytes() * 3 < cont.cache_bytes()
    assert cont.dispatches == paged.dispatches
    st = paged.kv_stats()
    assert st["peak_blocks"] == 6 and st["live_blocks"] == 6


def test_kv_page_counters_follow_executed_steps(paged_setup):
    """``kv_pages`` counts, per executed row-step, the ``ceil((pos + 1) /
    bs)`` pages the paged kernel attends and the ``nb`` table pages it
    could walk — through full windows, a window every row exits early
    from, and a single ``step``."""
    from repro.serving import DecodeRunner

    _, model, params, prompts = paged_setup
    bs = 4
    r = DecodeRunner(model, params, prompts, max_new_tokens=8, max_slots=2,
                     n_slots=4, kv_block_size=bs)
    nb = r._max_blocks
    want = {"attended": 0, "table": 0}

    def run(slots, n_steps, thr):
        pos = r._pos[slots].copy()
        if n_steps is None:
            r.step(slots, [0])
            nd = 1
        else:
            nd = r.step_multi(slots, [0], n_steps, np.full(1, thr, np.float32))[2].shape[0]
        for t in range(nd):
            want["attended"] += int((-(-(pos + t + 1) // bs)).sum())
        want["table"] += len(slots) * nd * nb
        return nd

    r.start(0, 0)
    assert run([0], 3, 0.0) == 3
    r.start(1, 1)
    assert run([0, 1], None, 0.0) == 1
    # every row's ramp fires at the first step: the window ends after it
    assert run([0, 1], 4, 2.0) == 1 and r.short_windows["early_end"] == 1
    assert run([0, 1], 2, 0.0) == 2
    assert r.kv_pages == want
    assert 0 < want["attended"] < want["table"]


def test_paged_cache_schema_covers_all_mixer_families():
    """Every mixer family now owns a paged page layout drawn from the one
    shared block pool: MLA pools the compressed latent streams, mamba
    pools per-slot state pages, local-window layers reuse the k/v token
    pools (ring-redirected through the first ceil(W/bs) table entries)."""
    from repro.configs import get_tiny
    from repro.models import build_model
    from repro.models.common import ParamInfo

    nb, bs = 4, 4
    mamba = build_model(get_tiny("mamba2-2.7b"))
    sch = mamba.paged_cache_schema(nb, bs)
    leaves = jax.tree.leaves(sch, is_leaf=lambda x: isinstance(x, ParamInfo))
    # state pages are per-slot, pool-leading, and NOT (P, bs, ...) token
    # shaped: conv (L?, P, d_conv-1, conv_dim) and ssm (L?, P, H, hp, N)
    assert leaves and all(nb in l.shape for l in leaves)
    assert not mamba.paged_sharing_ok

    mla = build_model(get_tiny("deepseek-v2-lite-16b"))
    cfg = mla.cfg
    sch = mla.paged_cache_schema(nb, bs)
    blk = sch["blocks"][0]
    assert set(blk) >= {"c", "k_pe"}
    assert blk["c"].shape[-2:] == (bs, cfg.kv_lora_rank)
    assert blk["k_pe"].shape[-2:] == (bs, cfg.qk_rope_dim)

    gemma = build_model(get_tiny("gemma3-4b").replace(decode_attn="paged"))
    sch = gemma.paged_cache_schema(nb, bs)
    leaves = jax.tree.leaves(sch, is_leaf=lambda x: isinstance(x, ParamInfo))
    assert leaves and all(l.shape[-3] == bs for l in leaves)
    # ring pages are position-aliased mod W: sharing refused
    assert not gemma.paged_sharing_ok


def test_runner_kv_block_size_validation(paged_setup):
    from repro.models import build_model
    from repro.serving import DecodeRunner

    cfg, model, params, prompts = paged_setup
    with pytest.raises(ValueError):
        DecodeRunner(model, params, prompts, kv_block_size=0)
    # kv_block_size=0 documents "contiguous" at the CLI: harmless on a
    # contiguous-cfg runner (must not divide by zero in __init__)
    cont = DecodeRunner(
        build_model(cfg.replace(decode_attn="ref")), params, prompts,
        max_new_tokens=4, kv_block_size=0,
    )
    assert not cont.paged
    cont.start(0, 0)
    cont.step([0], [0])
