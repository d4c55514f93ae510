"""Decoder-only LM covering all assigned transformer/SSM/hybrid archs.

The layer stack is described by a *plan*: an optional unrolled prefix, a
scanned period of heterogeneous slots, and an unrolled suffix. Parameters
for scanned slots carry a leading ``n_periods`` dim; everything inside one
period is unrolled in the scan body. This keeps HLO small (compile time ~
period size, not n_layers) while supporting interleave patterns
(gemma3 5:1 local:global, jamba 1 attn : 7 mamba, llama-vision cross-attn
every 5th layer, deepseek-v2 leading dense layer).

Early-exit ramps (the paper's technique) attach at block boundaries (cut
vertices): pooled hidden -> per-ramp RMSNorm -> per-ramp LM-head. All ramp
weights exist at every feasible site; serving gathers a dynamic
``active_sites`` subset so the active-ramp set changes with **zero
recompiles** (beyond-paper, TPU-native — see DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.compat import mesh_axis_size, shard_map
from repro.models import layers as LY
from repro.models import mamba as MB
from repro.models import moe as MOE
from repro.models.common import (
    ParamInfo,
    abstract_from_schema,
    init_from_schema,
    is_info,
    specs_from_schema,
)
from repro.models.layers import MeshAxes


def _tp_gather(axis_name, y):
    """Concatenate the per-device column slices of ``y`` along its last
    axis (device-order = column-order, so the result is the dense array)."""
    return jax.lax.all_gather(y, axis_name, axis=y.ndim - 1, tiled=True)


@dataclasses.dataclass(frozen=True)
class TpCtx:
    """Tensor-parallel context threaded through ``_block``/``decode`` when
    they run INSIDE a shard_map body (``decode_sharded``).

    The decomposition is the exactness-preserving one: activations stay
    replicated at sublayer boundaries; wq/wk/wv (and w_gate/w_up) are
    COLUMN-sliced so each device computes a contiguous head (hidden) block
    bitwise-identically to the corresponding slice of the dense matmul;
    wo/w_down are column-sliced along their OUTPUT dim so the final
    projections are also column slices of the dense result. Combines are
    tiled ``all_gather``s — pure concatenation, no arithmetic — so the
    whole block is bit-identical to single-device decode. (A Megatron
    row-split + psum combine reassociates the contraction and drifts by
    ULPs; it is deliberately not used.)

    m: model-axis size; gather: ``_tp_gather`` bound to the model axis (or
    a shape-only stub under the abstract probe); data_axes: data axis
    names when rows are additionally sharded over data (contiguous caches
    only), used to reduce row-wise predicates across data shards.
    """

    m: int
    gather: Any
    data_axes: Optional[Tuple[str, ...]] = None


@dataclasses.dataclass(frozen=True)
class SlotSpec:
    mixer: str = "attn"  # 'attn' | 'mla' | 'mamba'
    ffn: str = "dense"  # 'dense' | 'moe' | 'none'
    is_local: bool = False
    cross: bool = False


@dataclasses.dataclass(frozen=True)
class Plan:
    prefix: Tuple[SlotSpec, ...]
    period: Tuple[SlotSpec, ...]
    n_periods: int
    suffix: Tuple[SlotSpec, ...]

    @property
    def n_layers(self) -> int:
        return len(self.prefix) + self.n_periods * len(self.period) + len(self.suffix)

    def layer_specs(self) -> List[SlotSpec]:
        return (
            list(self.prefix)
            + [s for _ in range(self.n_periods) for s in self.period]
            + list(self.suffix)
        )


def build_plan(cfg) -> Plan:
    L = cfg.n_layers
    if cfg.ssm and not cfg.hybrid_period:  # mamba2
        return Plan((), (SlotSpec("mamba", "none"),), L, ())
    if cfg.hybrid_period:  # jamba
        p = cfg.hybrid_period
        period = tuple(
            SlotSpec(
                mixer=("attn" if i == p // 2 else "mamba"),
                ffn=("moe" if (cfg.moe and i % cfg.moe_every == 1) else "dense"),
            )
            for i in range(p)
        )
        assert L % p == 0, (L, p)
        return Plan((), period, L // p, ())
    if cfg.local_global_pattern:  # gemma3
        pat = cfg.local_global_pattern
        period = tuple(SlotSpec("attn", "dense", is_local=(i < pat)) for i in range(pat + 1))
        n = L // (pat + 1)
        rem = L - n * (pat + 1)
        suffix = tuple(SlotSpec("attn", "dense", is_local=True) for _ in range(rem))
        return Plan((), period, n, suffix)
    if cfg.cross_attn_every:  # llama-vision
        k = cfg.cross_attn_every
        period = tuple(
            SlotSpec("attn", "dense", cross=(i == k - 1)) for i in range(k)
        )
        assert L % k == 0, (L, k)
        return Plan((), period, L // k, ())
    mixer = "mla" if cfg.mla else "attn"
    ffn = "moe" if cfg.moe else "dense"
    prefix = tuple(SlotSpec(mixer, "dense") for _ in range(cfg.first_k_dense))
    return Plan(prefix, (SlotSpec(mixer, ffn),), L - cfg.first_k_dense, ())


# ---------------------------------------------------------------------------
# schema assembly


def _slot_schema(cfg, slot: SlotSpec, L=None) -> dict:
    sch: Dict[str, Any] = {"ln1": LY.norm_schema(cfg, L)}
    if slot.mixer == "attn":
        sch["mixer"] = LY.gqa_schema(cfg, L)
    elif slot.mixer == "mla":
        sch["mixer"] = LY.mla_schema(cfg, L)
    elif slot.mixer == "mamba":
        sch["mixer"] = MB.mamba_schema(cfg, L)
    if slot.cross:
        sch["lnx"] = LY.norm_schema(cfg, L)
        sch["xattn"] = LY.cross_attn_schema(cfg, L)
    if slot.ffn != "none":
        sch["ln2"] = LY.norm_schema(cfg, L)
        sch["ffn"] = MOE.moe_schema(cfg, L) if slot.ffn == "moe" else LY.ffn_schema(cfg, cfg.d_ff, L)
    return sch


def ramp_sites(cfg, max_sites: int = 12) -> Tuple[int, ...]:
    """Feasible ramp sites = block boundaries (cut vertices); thinned to at
    most `max_sites`, never including the final layer (that's the model)."""
    L = cfg.n_layers
    n = min(L - 1, max_sites)
    if n <= 0:
        return ()
    stride = (L - 1) / n
    sites = sorted({int(math.floor((i + 1) * stride)) - 1 for i in range(n)})
    return tuple(s for s in sites if 0 <= s < L - 1) or (0,)


def ramp_schema(cfg) -> dict:
    S = len(ramp_sites(cfg))
    d, Vp = cfg.d_model, cfg.padded_vocab
    dt = jnp.dtype(cfg.dtype)
    sch = {"norm_w": ParamInfo((S, d), jnp.float32, P(), "zeros")}
    if cfg.ramp_style != "tied":  # 'tied' shares the model's own LM head
        sch["head"] = ParamInfo((S, d, Vp), dt, P(None, "data", "model"), "normal:0.02")
    if cfg.ramp_style == "mlp":  # heavier ramps (paper Fig 9 comparison)
        sch["w1"] = ParamInfo((S, d, cfg.ramp_hidden), dt, P(None, "data", None), "normal:0.02")
        sch["w2"] = ParamInfo((S, cfg.ramp_hidden, d), dt, P(None, None, "data"), "normal:0.02")
    return sch


def paged_leaf_kinds(schema) -> List[str]:
    """Per-leaf kind labels for a paged cache schema, in ``jax.tree``
    flatten order (dicts iterate sorted keys). Kinds drive the serving
    runner's per-leaf scatter/gather branches:

    * ``"tokens"`` — per-token pages ``(P, bs, ...)``: attn k/v, MLA
      latent ``c``/``k_pe``. Prefill scatters prompt rows block-wise;
      appended every decode step.
    * ``"state"`` — per-slot pages ``(P, ...)``: mamba ``conv``/``ssm``.
      One page per slot (the first table entry); overwritten in place.
    * ``"xkv"`` — read-only pinned pages ``(P, bs, ...)``: cross-attn
      encoder k/v. Prefilled once, never appended.
    """
    out: List[str] = []

    def walk(node, kind):
        if is_info(node) or not isinstance(node, (dict, list, tuple)):
            out.append(kind)
            return
        if isinstance(node, dict):
            for kk in sorted(node):
                nk = "xkv" if kk == "xkv" else (
                    "state" if kk in ("conv", "ssm") else kind
                )
                walk(node[kk], nk)
        else:
            for v in node:
                walk(v, kind)

    walk(schema, "tokens")
    return out


class MultiStepDecodeMixin:
    """Multi-step fused-exit decode window, shared by every model class
    exposing a ``decode(params, cache, tokens, pos, ...)`` step (decoder
    LMs and the enc-dec decoder). The window is family-agnostic: the
    ``lax.while_loop`` advances EVERY row exactly ``n_done`` steps
    together and the host keeps exactly ``n_done`` tokens per row, so
    recurrent (mamba) state, ring wraparound, and read-only cross caches
    all stay consistent across early termination."""

    def decode_multi(self, params, cache, tokens, pos, n_steps, *, n_max,
                     active_sites=None, thresholds=None, row_valid=None,
                     axes=LY.TEST_AXES, mesh=None, moe_impl="ep",
                     block_tables=None, tp=None):
        """Up to ``n_steps`` greedy decode steps under ONE dispatch
        (`lax.while_loop`), with the exit decision taken ON DEVICE from a
        resident threshold vector — the host syncs once per window, not
        once per token.

        tokens: (B, 1) int32; pos: int32[B] per-row write indices (per-row
        is REQUIRED: every window row sits at its own offset). ``n_steps``
        is a traced scalar <= the static unroll bound ``n_max`` (callers
        bucket it so compile count stays bounded). ``thresholds`` is the
        (K,) f32 device-resident exit-threshold vector aligned with
        ``active_sites`` (strict ``<``; pad slots carry 0.0, which can
        never trigger). ``row_valid`` (B,) bool masks bucket-padding rows
        out of the all-exited test.

        Semantics (the staleness/accuracy contract, README "On-device
        exits & sync windows"):

        * every step runs the FULL model for every row — exits are
          *decisions*, not compute cuts, because the controller's
          agreement records need the final head's label for every token
          (replay-completeness). What the on-device mask gates is the
          WINDOW: once every valid row has exited, later steps are skipped
          and control returns to the host early.
        * thresholds are frozen across the window — deliberately stale
          between syncs. Records for every executed step are packed and
          streamed back at the sync boundary, so adaptation still sees
          every token; only the *decision* lag is traded for dispatch
          count. At ``n_steps == 1`` the decision uses the exact current
          thresholds: bit-identical to the per-step path.

        Returns ``(new_cache, (ramp_label (n_max,K,B), ramp_maxprob
        (n_max,K,B), final_label (n_max,B), exit_site (n_max,B), n_done))``
        — entries past ``n_done`` are garbage the caller must slice off.
        """
        B = tokens.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        if pos.ndim < 1:
            raise ValueError("decode_multi requires per-row pos: int32[B]")
        K = 0 if active_sites is None else int(jnp.shape(active_sites)[0])
        if K and thresholds is None:
            raise ValueError("decode_multi with active ramps needs thresholds")
        if row_valid is None:
            row_valid = jnp.ones((B,), bool)
        sites_arr = (jnp.asarray(active_sites, jnp.int32)
                     if K else jnp.zeros((0,), jnp.int32))
        thr = (jnp.asarray(thresholds, jnp.float32)
               if K else jnp.zeros((0,), jnp.float32))

        def body(carry):
            i, all_ex, cache, tok, p, rl, rm, fl, ex = carry
            cache, outs = self.decode(
                params, cache, tok, p, active_sites=active_sites, axes=axes,
                mesh=mesh, moe_impl=moe_impl, block_tables=block_tables,
                exit_thresholds=(thr if K else None),
                # subclasses (EncDecLM) override decode without the tp
                # kwarg; only the TP shard_map body threads a context
                **({"tp": tp} if tp is not None else {}),
            )
            f = outs["final"]["label"].reshape(-1).astype(jnp.int32)  # (B,)
            if K:
                lab = outs["ramps"]["label"].astype(jnp.int32)  # (K, B)
                mp = outs["ramps"]["maxprob"].astype(jnp.float32)
                # per-ramp on-device mask (fused into the pallas head when
                # enabled); argmax returns the FIRST true row = the
                # shallowest exiting site (active_sites ascending)
                mask = outs["ramps"]["exit"].astype(bool)
                anyx = jnp.any(mask, axis=0)
                site = jnp.where(
                    anyx, sites_arr[jnp.argmax(mask, axis=0)], -1
                ).astype(jnp.int32)
            else:
                lab = jnp.zeros((0, B), jnp.int32)
                mp = jnp.zeros((0, B), jnp.float32)
                site = jnp.full((B,), -1, jnp.int32)
            rl = jax.lax.dynamic_update_slice(rl, lab[None], (i, 0, 0))
            rm = jax.lax.dynamic_update_slice(rm, mp[None], (i, 0, 0))
            fl = jax.lax.dynamic_update_slice(fl, f[None], (i, 0))
            ex = jax.lax.dynamic_update_slice(ex, site[None], (i, 0))
            all_ex = jnp.all(jnp.logical_or(~row_valid, site >= 0))
            if tp is not None and tp.data_axes:
                # rows are sharded over data: the window terminates only
                # when EVERY shard's rows have exited — reduce the local
                # predicate across the data axes (replicated over model)
                all_ex = jax.lax.psum(
                    jnp.logical_not(all_ex).astype(jnp.int32), tp.data_axes
                ) == 0
            return (i + 1, all_ex, cache, f.reshape(-1, 1), p + 1,
                    rl, rm, fl, ex)

        def cond(carry):
            i, all_ex = carry[0], carry[1]
            return jnp.logical_and(i < jnp.int32(n_steps),
                                   jnp.logical_not(all_ex))

        init = (
            jnp.int32(0), jnp.asarray(False), cache, tokens, pos,
            jnp.zeros((n_max, K, B), jnp.int32),
            jnp.zeros((n_max, K, B), jnp.float32),
            jnp.zeros((n_max, B), jnp.int32),
            jnp.full((n_max, B), -1, jnp.int32),
        )
        n_done, _, cache, _, _, rl, rm, fl, ex = jax.lax.while_loop(
            cond, body, init
        )
        return cache, (rl, rm, fl, ex, n_done)


class LM(MultiStepDecodeMixin):
    """Functional model wrapper (see DESIGN.md §3)."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.plan = build_plan(cfg)
        self.sites = ramp_sites(cfg)

    # -- schema / init ------------------------------------------------------

    def schema(self) -> dict:
        cfg, plan = self.cfg, self.plan
        sch: Dict[str, Any] = {"tok": LY.embed_schema(cfg)}
        if plan.prefix:
            sch["prefix"] = [_slot_schema(cfg, s) for s in plan.prefix]
        sch["blocks"] = [_slot_schema(cfg, s, L=plan.n_periods) for s in plan.period]
        if plan.suffix:
            sch["suffix"] = [_slot_schema(cfg, s) for s in plan.suffix]
        sch["final_norm"] = LY.norm_schema(cfg)
        sch["ramps"] = ramp_schema(cfg)
        if cfg.cross_attn_every:
            sch["frontend"] = {
                "proj": ParamInfo(
                    (cfg.d_frontend, cfg.d_model), jnp.dtype(cfg.dtype), P(None, "model"), "normal:0.02"
                )
            }
        return sch

    def init(self, key) -> dict:
        return init_from_schema(self.schema(), key)

    def pspecs(self, axes: MeshAxes) -> dict:
        return specs_from_schema(LY.resolve_schema(self.schema(), axes))

    def abstract(self) -> dict:
        return abstract_from_schema(self.schema())

    # -- cache --------------------------------------------------------------

    def _slot_cache_schema(self, cfg, slot: SlotSpec, B, S, shard_batch, L=None):
        dt = jnp.dtype(cfg.dtype)
        pre = () if L is None else (L,)
        pfx = (None,) * len(pre)
        bspec, sspec = ("data", None) if shard_batch else (None, "data")
        if cfg.kv_seq_shard:
            # flash-decode layout: seq sharded over `model` (softmax partials
            # psum small stats instead of all-reducing full score tensors)
            sspec = ("data", "model") if not shard_batch else "model"
        if slot.mixer == "attn":
            K, hd = cfg.n_kv_heads, cfg.hd
            hspec = ("model" if hd % 16 == 0 else None) if not cfg.kv_seq_shard else None
            Sl = S
            if cfg.windowed_cache and slot.is_local and cfg.window:
                Sl = min(cfg.window, S)
            c = {
                "k": ParamInfo(pre + (B, Sl, K, hd), dt, P(*pfx, bspec, sspec, None, hspec), "zeros"),
                "v": ParamInfo(pre + (B, Sl, K, hd), dt, P(*pfx, bspec, sspec, None, hspec), "zeros"),
            }
        elif slot.mixer == "mla":
            r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
            c = {
                "c": ParamInfo(pre + (B, S, r), dt, P(*pfx, bspec, sspec, None), "zeros"),
                "k_pe": ParamInfo(pre + (B, S, dr), dt, P(*pfx, bspec, sspec, None), "zeros"),
            }
        elif slot.mixer == "mamba":
            c = MB.mamba_cache_schema(cfg, B, L=None)
            # add period dim manually
            if L is not None:
                c = jax.tree.map(
                    lambda i: ParamInfo((L,) + i.shape, i.dtype, P(None, *i.spec), i.init),
                    c,
                    is_leaf=is_info,
                )
        else:
            c = {}
        if slot.cross:
            K, hd = cfg.n_kv_heads, cfg.hd
            M = cfg.n_image_tokens
            hspec = "model" if hd % 16 == 0 else None
            c["xkv"] = {
                "k": ParamInfo(pre + (B, M, K, hd), dt, P(*pfx, bspec, None, None, hspec), "zeros"),
                "v": ParamInfo(pre + (B, M, K, hd), dt, P(*pfx, bspec, None, None, hspec), "zeros"),
            }
        return c

    def _slot_paged_cache_schema(self, cfg, slot: SlotSpec, n_blocks, bs, L=None):
        """Paged (block-pool) analogue of ``_slot_cache_schema``. Every
        mixer family draws pages from the same refcounted block pool, each
        with its own page layout:

        * full attention: k/v pools ``(P, bs, K, hd)`` — virtual token
          ``t`` lives at ``(table[b, t // bs], t % bs)``.
        * local (ring) attention: same k/v pools, but the write index is
          ``pos % W`` redirected through the table — only the first
          ``ceil(W/bs)`` table entries are ever touched, so the live
          window stays W-bounded inside the shared pool.
        * MLA: pools over the compressed latent streams ``c (P, bs, r)``
          and ``k_pe (P, bs, dr)`` — one shared stream per layer (the
          latent cache is MQA-like), not per-head.
        * mamba: per-SLOT state pages ``conv (P, d_conv-1, conv_dim)`` /
          ``ssm (P, H, hp, N)`` living in the slot's FIRST table entry.
          State is O(1) per slot (not per token), so one page holds it —
          share/CoW degenerate to private allocation (enforced by the
          runner: prefix sharing is refused for these models).
        * cross-attention: read-only ``xkv`` pools ``(P, bs, K, hd)``
          prefilled once and refcount-pinned; their block ids ride in the
          LAST ``ceil(M/bs)`` table columns and are never appended.
        """
        dt = jnp.dtype(cfg.dtype)
        pre = () if L is None else (L,)
        pfx = (None,) * len(pre)
        if slot.mixer == "attn" or slot.cross:
            # pure-SSM configs have n_heads=0: only touch head_dim when an
            # attention leaf actually needs it
            K, hd = cfg.n_kv_heads, cfg.hd
            hspec = "model" if hd % 16 == 0 else None
        if slot.mixer == "attn":
            shp = pre + (n_blocks, bs, K, hd)
            c = {
                "k": ParamInfo(shp, dt, P(*pfx, None, None, None, hspec), "zeros"),
                "v": ParamInfo(shp, dt, P(*pfx, None, None, None, hspec), "zeros"),
            }
        elif slot.mixer == "mla":
            r, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
            c = {
                "c": ParamInfo(pre + (n_blocks, bs, r), dt, P(*pfx, None, None, None), "zeros"),
                "k_pe": ParamInfo(pre + (n_blocks, bs, dr), dt, P(*pfx, None, None, None), "zeros"),
            }
        elif slot.mixer == "mamba":
            c = MB.mamba_paged_cache_schema(cfg, n_blocks, L=L)
        else:
            c = {}
        if slot.cross:
            shp = pre + (n_blocks, bs, K, hd)
            c["xkv"] = {
                "k": ParamInfo(shp, dt, P(*pfx, None, None, None, hspec), "zeros"),
                "v": ParamInfo(shp, dt, P(*pfx, None, None, None, hspec), "zeros"),
            }
        return c

    def paged_cache_schema(self, n_blocks: int, block_size: int) -> dict:
        """Cache schema for the paged decode layout: same tree structure as
        ``cache_schema`` but every attention leaf is a block pool shared by
        all slots — total KV memory is ``n_blocks * block_size`` tokens,
        independent of slot count."""
        cfg, plan = self.cfg, self.plan
        sch: Dict[str, Any] = {}
        if plan.prefix:
            sch["prefix"] = [
                self._slot_paged_cache_schema(cfg, s, n_blocks, block_size)
                for s in plan.prefix
            ]
        sch["blocks"] = [
            self._slot_paged_cache_schema(cfg, s, n_blocks, block_size, L=plan.n_periods)
            for s in plan.period
        ]
        if plan.suffix:
            sch["suffix"] = [
                self._slot_paged_cache_schema(cfg, s, n_blocks, block_size)
                for s in plan.suffix
            ]
        return sch

    def init_paged_cache(self, n_blocks: int, block_size: int) -> dict:
        return jax.tree.map(
            lambda i: jnp.zeros(i.shape, i.dtype),
            self.paged_cache_schema(n_blocks, block_size),
            is_leaf=is_info,
        )

    def paged_cache_kinds(self, n_blocks: int, block_size: int) -> list:
        """Flat per-leaf kind labels for ``paged_cache_schema`` (see
        ``paged_leaf_kinds``)."""
        return paged_leaf_kinds(self.paged_cache_schema(n_blocks, block_size))

    def paged_xkv_blocks(self, block_size: int) -> int:
        """Number of extra TRAILING block-table columns holding the pinned
        read-only cross-attention pages (0 for models without cross
        layers). The runner widens every table it ships by this amount."""
        if not any(s.cross for s in self.plan.layer_specs()):
            return 0
        return -(-self.cfg.n_image_tokens // block_size)

    @property
    def paged_sharing_ok(self) -> bool:
        """Whether prefix sharing / copy-on-write are sound for this plan.
        Sharing moves *token* pages between tables; mamba state pages are
        per-slot recurrent state, ring pages are position-aliased mod W,
        and xkv pages are pinned per slot — none of those share, so the
        runner refuses ``prefix_cache`` unless every layer is plain
        full attention."""
        cfg = self.cfg
        return all(
            s.mixer == "attn" and not s.cross and not (s.is_local and cfg.window)
            for s in self.plan.layer_specs()
        )

    def cache_schema(self, B: int, S: int, shard_batch: bool = True) -> dict:
        cfg, plan = self.cfg, self.plan
        sch: Dict[str, Any] = {}
        if plan.prefix:
            sch["prefix"] = [
                self._slot_cache_schema(cfg, s, B, S, shard_batch) for s in plan.prefix
            ]
        sch["blocks"] = [
            self._slot_cache_schema(cfg, s, B, S, shard_batch, L=plan.n_periods)
            for s in plan.period
        ]
        if plan.suffix:
            sch["suffix"] = [
                self._slot_cache_schema(cfg, s, B, S, shard_batch) for s in plan.suffix
            ]
        return sch

    def init_cache(self, B: int, S: int) -> dict:
        return jax.tree.map(
            lambda i: jnp.zeros(i.shape, i.dtype), self.cache_schema(B, S), is_leaf=is_info
        )

    def cache_pspecs(self, B, S, axes: MeshAxes, shard_batch=True) -> dict:
        return specs_from_schema(
            LY.resolve_schema(self.cache_schema(B, S, shard_batch), axes)
        )

    # -- forward ------------------------------------------------------------

    def _block(
        self,
        slot: SlotSpec,
        p,
        h,
        *,
        positions,
        mask_full,
        mask_local,
        axes,
        mesh,
        cache,
        cache_index,
        memory,
        moe_impl,
        block_tables=None,
        rope_theta_local=10_000.0,
        tp: Optional[TpCtx] = None,
    ):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        x = LY.apply_norm(cfg, p["ln1"], h)
        new_cache = dict(cache) if cache is not None else None
        if slot.mixer == "attn":
            mask = mask_local if slot.is_local else mask_full
            theta = rope_theta_local if slot.is_local else cfg.rope_theta
            sub = {k: cache[k] for k in ("k", "v")} if cache is not None else None
            # ring layout: the windowed-cache optimization (contiguous) OR
            # any paged local layer — the block pool always ring-pages
            # local windows through the first ceil(W/bs) table entries
            # (without the redirection a paged local layer would attend
            # full-causal, silently breaking the window semantics).
            ring = (
                cfg.window
                if (slot.is_local and cfg.window
                    and (cfg.windowed_cache or block_tables is not None))
                else None
            )
            # local layer on a FULL contiguous cache: window-gather decode
            lw = cfg.window if (slot.is_local and cfg.window and ring is None) else None
            ci = cache_index
            if ring is not None and ci is not None and block_tables is None:
                ci = cache_index % ring  # ring slot at decode
            # local windowed layers keep the dense masked path (the flash
            # wrapper only knows "attend to <= pos"); everything else routes
            # single-token decode through kernels/decode_attention. Paged
            # ring layers keep the TRUE position (the paged branch derives
            # both the ring write slot and the ring mask from it).
            if block_tables is not None:
                impl = cfg.decode_attn
            else:
                impl = "dense" if (slot.is_local and cfg.window) else cfg.decode_attn
            if tp is not None and tp.m > 1:
                # per-device head slice: the sliced cfg pins head_dim
                # explicitly (the `hd` property would re-derive it from the
                # sliced n_heads otherwise) and keeps the GQA group size
                # H/K unchanged, so contiguous kv-head blocks stay aligned
                # with their query-head groups. `out_proj=False` returns
                # the raw (B,S,Hl*hd) head block; wo is applied AFTER the
                # head gather as an output-column slice.
                cfg_l = cfg.replace(
                    n_heads=cfg.n_heads // tp.m,
                    n_kv_heads=cfg.n_kv_heads // tp.m,
                    head_dim=cfg.hd,
                )
                out, nc = LY.attn_apply(
                    cfg_l, p["mixer"], x, positions=positions, mask=mask,
                    axes=axes, mesh=mesh, cache=sub, cache_index=ci,
                    rope_theta=theta, ring_window=ring, local_window=lw,
                    decode_impl=impl, block_table=block_tables,
                    out_proj=False,
                )
                out = tp.gather(tp.gather(out) @ p["mixer"]["wo"])
            else:
                out, nc = LY.attn_apply(
                    cfg, p["mixer"], x, positions=positions, mask=mask, axes=axes,
                    mesh=mesh, cache=sub, cache_index=ci, rope_theta=theta,
                    ring_window=ring, local_window=lw, decode_impl=impl,
                    block_table=block_tables,
                )
            if nc is not None:
                new_cache.update(nc)
        elif slot.mixer == "mla":
            sub = {k: cache[k] for k in ("c", "k_pe")} if cache is not None else None
            out, nc = LY.mla_apply(
                cfg, p["mixer"], x, positions=positions, mask=mask_full, axes=axes,
                mesh=mesh, cache=sub, cache_index=cache_index,
                absorbed=getattr(cfg, "mla_absorbed", False),
                decode_impl=cfg.decode_attn, block_table=block_tables,
            )
            if nc is not None:
                new_cache.update(nc)
        elif slot.mixer == "mamba":
            sub = (
                {k: cache[k] for k in ("conv", "ssm")} if cache is not None else None
            )
            if block_tables is not None:
                # block-pooled SSM state: the slot's whole recurrent state
                # lives in the page at its FIRST table entry (state is O(1)
                # per slot, not per token). Duplicate bucket-padding rows
                # scatter identical values; free rows hit the trash block.
                blk0 = jnp.asarray(block_tables, jnp.int32)[:, 0]
                view = {"conv": sub["conv"][blk0], "ssm": sub["ssm"][blk0]}
                out, st = MB.mamba_apply(
                    cfg, p["mixer"], x, axes=axes, mesh=mesh, cache=view
                )
                nc = {
                    "conv": sub["conv"].at[blk0].set(st["conv"].astype(sub["conv"].dtype)),
                    "ssm": sub["ssm"].at[blk0].set(st["ssm"].astype(sub["ssm"].dtype)),
                }
            else:
                out, nc = MB.mamba_apply(cfg, p["mixer"], x, axes=axes, mesh=mesh, cache=sub)
            if nc is not None:
                new_cache.update(nc)
        h = h + out
        if slot.cross:
            xx = LY.apply_norm(cfg, p["lnx"], h)
            kvc = cache.get("xkv") if cache is not None else None
            if block_tables is not None and kvc is not None:
                # read-only pinned xkv pages: gather the M encoder tokens
                # from the trailing table columns; never written back.
                bsz = kvc["k"].shape[1]
                M = cfg.n_image_tokens
                nbx = -(-M // bsz)
                xtab = jnp.asarray(block_tables, jnp.int32)[:, -nbx:]
                Bq = xtab.shape[0]

                def _gather(pool):
                    g = pool[xtab]  # (B, nbx, bs, K, hd)
                    return g.reshape((Bq, nbx * bsz) + pool.shape[2:])[:, :M]

                out, _ = LY.cross_attn_apply(
                    cfg, p["xattn"], xx, memory=None,
                    kv_cache={"k": _gather(kvc["k"]), "v": _gather(kvc["v"])},
                    axes=axes, mesh=mesh,
                )
                if new_cache is not None:
                    new_cache["xkv"] = kvc
            else:
                out, kv = LY.cross_attn_apply(
                    cfg, p["xattn"], xx, memory=memory, kv_cache=kvc, axes=axes, mesh=mesh
                )
                if new_cache is not None:
                    new_cache["xkv"] = kv
            h = h + out
        if slot.ffn != "none":
            x = LY.apply_norm(cfg, p["ln2"], h)
            if slot.ffn == "moe":
                if tp is not None and tp.m > 1 and moe_impl == "ep":
                    # expert-parallel inside the TP shard_map body: reuse
                    # the lifted per-device dispatch (no nested shard_map)
                    out, a = MOE.moe_apply_ep_device(cfg, p["ffn"], x, axes, tp.m)
                else:
                    out, a = MOE.moe_apply(cfg, p["ffn"], x, axes, mesh, impl=moe_impl)
                aux = aux + a
            else:
                if tp is not None and tp.m > 1:
                    out = LY.ffn_apply_tp(cfg, p["ffn"], x, tp.gather)
                else:
                    out = LY.ffn_apply(cfg, p["ffn"], x, axes, mesh)
            h = h + out
        return h, new_cache, aux

    def _stack(
        self,
        params,
        h,
        *,
        positions,
        mask_full,
        mask_local,
        axes,
        mesh,
        caches,
        cache_index,
        memory,
        moe_impl,
        pool_idx,
        block_tables=None,
        remat=False,
        tp: Optional[TpCtx] = None,
        layer_params=None,
    ):
        """Run prefix + scanned periods + suffix. Returns
        (h, pooled (L,B,npos,d), new_caches, aux). ``layer_params(kind, i,
        p)`` maps the params ``p`` of layer slot ``i`` of ``kind``
        ('prefix' | 'blocks' | 'suffix'; one period's slice for 'blocks')
        to the params the layer runs with."""
        cfg, plan = self.cfg, self.plan
        pooled_all: List = []
        aux_total = jnp.zeros((), jnp.float32)

        def pool(hh):
            return jnp.take(hh, pool_idx, axis=1)  # (B, npos, d)

        kw = dict(
            positions=positions, mask_full=mask_full, mask_local=mask_local,
            axes=axes, mesh=mesh, cache_index=cache_index, memory=memory,
            moe_impl=moe_impl, block_tables=block_tables, tp=tp,
        )
        lp = layer_params or (lambda kind, i, p: p)
        new_caches: Dict[str, Any] = {}
        if plan.prefix:
            new_caches["prefix"] = []
            for i, slot in enumerate(plan.prefix):
                c = caches["prefix"][i] if caches else None
                h, nc, a = self._block(slot, lp("prefix", i, params["prefix"][i]),
                                       h, cache=c, **kw)
                new_caches["prefix"].append(nc)
                aux_total = aux_total + a
                pooled_all.append(pool(h))

        def body(carry, xs):
            hh, auxc = carry
            pblocks, cblocks = xs
            pooled_s, cout = [], []
            for s, slot in enumerate(plan.period):
                c = cblocks[s] if cblocks is not None else None
                hh, nc, a = self._block(slot, lp("blocks", s, pblocks[s]), hh,
                                        cache=c, **kw)
                auxc = auxc + a
                pooled_s.append(pool(hh))
                cout.append(nc if nc is not None else 0)
            return (hh, auxc), (jnp.stack(pooled_s), cout)

        if remat:
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if cfg.remat_policy == "dots"
                else None  # save nothing: recompute everything
            )
            body = jax.checkpoint(body, policy=policy)
        cblocks = caches["blocks"] if caches else None
        (h, aux_total), (pooled_scan, cache_scan) = jax.lax.scan(
            body, (h, aux_total), (params["blocks"], cblocks),
            unroll=True if cfg.scan_unroll else 1,
        )
        # pooled_scan: (n_periods, n_slots, B, npos, d) -> flatten layer-major
        ps = pooled_scan.reshape((-1,) + pooled_scan.shape[2:])
        new_caches["blocks"] = cache_scan if caches else None

        if plan.suffix:
            new_caches["suffix"] = []
            for i, slot in enumerate(plan.suffix):
                c = caches["suffix"][i] if caches else None
                h, nc, a = self._block(slot, lp("suffix", i, params["suffix"][i]),
                                       h, cache=c, **kw)
                new_caches["suffix"].append(nc)
                aux_total = aux_total + a
                pooled_all.append(pool(h))

        # assemble pooled (L, B, npos, d): prefix ++ scan ++ suffix
        n_pre = len(plan.prefix)
        parts = []
        if n_pre:
            parts.append(jnp.stack(pooled_all[:n_pre]))
        parts.append(ps)
        if plan.suffix:
            parts.append(jnp.stack(pooled_all[n_pre:]))
        pooled = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        return h, pooled, (new_caches if caches else None), aux_total

    # -- ramp heads ----------------------------------------------------------

    def ramp_outputs(self, params, pooled, site_idx=None, stop_grad=True,
                     axes=None, mesh=None):
        """pooled: (L,B,npos,d). site_idx: int32[K] (dynamic) or None=all
        sites. Returns ramp logits (K,B,npos,Vp) in f32, vocab-sharded."""
        cfg = self.cfg
        sites = jnp.asarray(self.sites, jnp.int32)
        if site_idx is None:
            site_idx = jnp.arange(len(self.sites), dtype=jnp.int32)
        layer_idx = sites[site_idx]
        hs = jnp.take(pooled, layer_idx, axis=0)  # (K,B,npos,d)
        if stop_grad:
            hs = jax.lax.stop_gradient(hs)
        nw = jnp.take(params["ramps"]["norm_w"], site_idx, axis=0)  # (K,d)
        hs = LY.rms_norm(hs, nw[:, None, None, :])
        if cfg.ramp_style == "mlp":
            w1 = jnp.take(params["ramps"]["w1"], site_idx, axis=0)
            w2 = jnp.take(params["ramps"]["w2"], site_idx, axis=0)
            hs = hs + jnp.einsum(
                "kbnh,khd->kbnd", jax.nn.gelu(jnp.einsum("kbnd,kdh->kbnh", hs, w1)), w2
            )
        if cfg.ramp_style == "tied":
            hw = params["tok"]["embed"].T if cfg.tie_embeddings else params["tok"]["lm_head"]
            out = jnp.einsum("kbnd,dv->kbnv", hs, hw).astype(jnp.float32)
        else:
            hw = jnp.take(params["ramps"]["head"], site_idx, axis=0)  # (K,d,Vp)
            out = jnp.einsum("kbnd,kdv->kbnv", hs, hw).astype(jnp.float32)
        if axes is not None:
            # keep vocab sharded over `model` (a d-contraction against an
            # FSDP-sharded head otherwise all-reduces full f32 logits)
            out = LY.constrain(out, axes.aspec(None, "data", None, "model"), mesh)
        return out

    # -- public entry points --------------------------------------------------

    def loss(self, params, batch, *, axes=LY.TEST_AXES, mesh=None, moe_impl="ep",
             remat=False, ramp_positions=16, train_mode="full"):
        """batch: {'tokens': (B,S) int32, 'labels': (B,S) int32 (-1 = pad)}.
        Returns (loss, metrics). Ramp losses always use stop-grad features
        (paper: backbone frozen w.r.t. ramps; ramps trained on all inputs)."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        B, S = tokens.shape
        positions = jnp.arange(S)[None, :]
        h = LY.embed_apply(cfg, params["tok"], tokens, positions)
        h = LY.constrain(h, axes.aspec("data", None, None), mesh)
        mask_full = LY.causal_mask(S, S, 0)
        mask_local = LY.window_mask(S, S, 0, cfg.window) if cfg.window else mask_full
        npos = min(ramp_positions, S)
        pool_idx = jnp.linspace(S // npos - 1, S - 1, npos).astype(jnp.int32)
        memory = None
        if cfg.cross_attn_every:
            memory = batch["image_embeds"] @ params["frontend"]["proj"]
        h, pooled, _, aux = self._stack(
            params, h, positions=positions, mask_full=mask_full,
            mask_local=mask_local, axes=axes, mesh=mesh, caches=None,
            cache_index=None, memory=memory, moe_impl=moe_impl,
            pool_idx=pool_idx, remat=remat,
        )
        h = LY.apply_norm(cfg, params["final_norm"], h)
        logits = LY.unembed(cfg, params["tok"], h)
        logits = LY.constrain(logits, axes.aspec("data", None, "model"), mesh)
        lm = _masked_ce(cfg, logits, labels)
        if len(self.sites):
            ramp_logits = self.ramp_outputs(params, pooled, axes=axes, mesh=mesh)
            R = ramp_logits.shape[0]
            ramp_labels = jnp.take(labels, pool_idx, axis=1)  # (B,npos)
            rloss = _masked_ce(
                cfg,
                ramp_logits.reshape(R * B, npos, -1),
                jnp.tile(ramp_labels, (R, 1)),
            )
        else:  # reduced-depth metric lowerings can have zero ramp sites
            rloss = jnp.zeros((), jnp.float32)
        if train_mode == "ramps_only":
            loss = rloss + 0.0 * lm
        else:
            loss = lm + rloss + 0.01 * aux
        return loss, {"lm_loss": lm, "ramp_loss": rloss, "moe_aux": aux}

    def prefill(self, params, tokens, *, cache_len=None, active_sites=None,
                axes=LY.TEST_AXES, mesh=None, moe_impl="ep", image_embeds=None,
                shard_batch=True, with_cache=True, layer_params=None):
        """tokens: (B,S). Returns (cache|None, outs) where outs carries final
        + per-active-ramp stats for the LAST position (the generated token).
        ``layer_params`` (see ``_stack``) rebuilds each layer's weights
        just before the layer runs — the tensor-parallel runner gathers a
        layer's shards there, so no device holds the whole model at once."""
        cfg = self.cfg
        B, S = tokens.shape
        cache_len = cache_len or S
        positions = jnp.arange(S)[None, :]
        h = LY.embed_apply(cfg, params["tok"], tokens, positions)
        h = LY.constrain(h, axes.aspec("data", None, None), mesh)
        mask_full = LY.causal_mask(S, cache_len, 0) if with_cache else LY.causal_mask(S, S, 0)
        if cfg.window:
            # local prefill attention ALWAYS runs against the in-flight
            # (S-long) k/v, never the padded cache: ring and full caches
            # then compute the identical S-column reduction (a cache_len
            # reduction regroups the sum and drifts by ULPs)
            mask_local = LY.window_mask(S, S, 0, cfg.window)
        else:
            mask_local = mask_full
        pool_idx = jnp.asarray([S - 1], jnp.int32)
        memory = None
        if cfg.cross_attn_every and image_embeds is not None:
            memory = image_embeds @ params["frontend"]["proj"]
        caches = self.init_cache(B, cache_len) if with_cache else None
        h, pooled, caches, _ = self._stack(
            params, h, positions=positions, mask_full=mask_full,
            mask_local=mask_local, axes=axes, mesh=mesh, caches=caches,
            cache_index=0, memory=memory, moe_impl=moe_impl, pool_idx=pool_idx,
            layer_params=layer_params,
        )
        outs = self._head_stats(params, h[:, -1:], pooled, active_sites,
                                axes=axes, mesh=mesh)
        return caches, outs

    def decode(self, params, cache, tokens, pos, *, active_sites=None,
               axes=LY.TEST_AXES, mesh=None, moe_impl="ep", block_tables=None,
               exit_thresholds=None, tp: Optional[TpCtx] = None,
               with_logits=False):
        """One decode step. tokens: (B,1); pos: int32 scalar (shared write
        index) or int32[B] per-row write indices — batched slot caches where
        continuous batching leaves every row at its own position (each row
        scatters its token and masks its own history).

        With ``block_tables`` (int32[B, max_blocks]) the cache is the PAGED
        block pool from ``init_paged_cache``: each row's token scatters to
        ``(block_tables[b, pos[b] // bs], pos[b] % bs)`` and attention walks
        the table (``cfg.decode_attn`` must be a 'paged*' variant); masks
        are internal to the paged kernel, so none are built here.
        ``with_logits`` adds the final head's f32 logits (B, Vp) as
        ``outs["final"]["logits"]`` (dense head). Returns (new_cache, outs)."""
        cfg = self.cfg
        B, S = tokens.shape
        assert S == 1
        pos = jnp.asarray(pos, jnp.int32)
        per_row = pos.ndim >= 1
        positions = pc = pos.reshape(-1, 1)  # (B, 1) per-row | (1, 1) shared
        h = LY.embed_apply(cfg, params["tok"], tokens, positions)
        if block_tables is not None:
            if not per_row:
                raise ValueError("paged decode requires per-row pos: int32[B]")
            mask_full = mask_local = None
            pool_idx = jnp.asarray([0], jnp.int32)
            h, pooled, new_cache, _ = self._stack(
                params, h, positions=positions, mask_full=None, mask_local=None,
                axes=axes, mesh=mesh, caches=cache, cache_index=pos.reshape(-1),
                memory=None, moe_impl=moe_impl, pool_idx=pool_idx,
                block_tables=jnp.asarray(block_tables, jnp.int32), tp=tp,
            )
            outs = self._head_stats(params, h, pooled, active_sites,
                                    axes=axes, mesh=mesh,
                                    exit_thresholds=exit_thresholds,
                                    with_logits=with_logits)
            return new_cache, outs
        # cache length from any attn cache leaf (mamba-only models have none)
        try:
            Sc = _cache_len(cache)
            kpos = jnp.arange(Sc)[None, :]
            mask_full = (kpos <= pc)[:, None, None, :]
            if cfg.windowed_cache and cfg.window:
                # ring semantics: attn_apply gathers the W ring slots back
                # into chronological order (positions pos-W+1..pos), so the
                # mask only blanks the pre-wrap columns (tpos < 0)
                j = jnp.arange(cfg.window)[None, :]
                mask_local = (pc - (cfg.window - 1) + j >= 0)[:, None, None, :]
            elif cfg.window:
                mask_local = ((kpos <= pc) & (kpos > pc - cfg.window))[:, None, None, :]
            else:
                mask_local = mask_full
        except ValueError:
            mask_full = mask_local = None
        pool_idx = jnp.asarray([0], jnp.int32)
        h, pooled, new_cache, _ = self._stack(
            params, h, positions=positions, mask_full=mask_full,
            mask_local=mask_local, axes=axes, mesh=mesh, caches=cache,
            cache_index=(pos.reshape(-1) if per_row else pos), memory=None,
            moe_impl=moe_impl, pool_idx=pool_idx, tp=tp,
        )
        outs = self._head_stats(params, h, pooled, active_sites,
                                axes=axes, mesh=mesh,
                                exit_thresholds=exit_thresholds,
                                with_logits=with_logits)
        return new_cache, outs

    # -- sharded (tensor-parallel) decode ------------------------------------

    def tp_check(self, tp: int, *, dp: int = 1, paged: bool = True, batch=None):
        """Raise ``NotImplementedError`` (with a why-note the support
        matrix surfaces verbatim) when this plan/config cannot run the
        tensor-parallel sharded-decode path at the given mesh shape."""
        cfg = self.cfg
        if tp <= 1 and dp <= 1:
            return
        for slot in self.plan.layer_specs():
            if slot.mixer == "mamba":
                raise NotImplementedError(
                    "tensor-parallel decode cannot shard the mamba mixer: the "
                    "SSM recurrence is per-row/per-channel with conv and state "
                    "fused, so no head axis divides across devices"
                )
            if slot.mixer == "mla":
                raise NotImplementedError(
                    "MLA shares one compressed latent stream across all heads; "
                    "every head shard still needs the full latent cache, so "
                    "sharding gives no per-device KV scaling"
                )
            if slot.cross:
                raise NotImplementedError(
                    "cross-attention slots pin per-slot read-only encoder "
                    "pages that sit outside the TP-sharded KV pool"
                )
        if tp > 1:
            if cfg.n_heads % tp:
                raise NotImplementedError(
                    f"n_heads={cfg.n_heads} not divisible by tp={tp}"
                )
            if cfg.n_kv_heads % tp:
                raise NotImplementedError(
                    f"n_kv_heads={cfg.n_kv_heads} not divisible by tp={tp} "
                    "(the KV pool shards by kv head, one contiguous block per "
                    "device)"
                )
            if cfg.d_ff % tp:
                raise NotImplementedError(
                    f"d_ff={cfg.d_ff} not divisible by tp={tp}"
                )
            if cfg.d_model % tp:
                raise NotImplementedError(
                    f"d_model={cfg.d_model} not divisible by tp={tp}"
                )
            if cfg.moe and cfg.n_experts % tp:
                raise NotImplementedError(
                    f"n_experts={cfg.n_experts} not divisible by tp={tp} "
                    "(expert-parallel MoE owns E/tp experts per device)"
                )
        if dp > 1:
            if paged:
                raise NotImplementedError(
                    "paged pools cannot shard rows over data: per-shard pool "
                    "scatters would diverge the replicated pool copies; "
                    "paged sharded decode is tensor-parallel only"
                )
            if batch is not None and batch % dp:
                raise NotImplementedError(
                    f"decode batch {batch} not divisible by data-parallel "
                    f"degree {dp}"
                )

    def tp_param_specs(self, axes: MeshAxes, *, moe_ep: bool = False) -> dict:
        """Per-leaf shard_map in_specs for params under tensor-parallel
        decode. Everything replicates except: wq/wk/wv/w_gate/w_up column
        slices (contiguous per-head / hidden blocks), wo/w_down column
        slices on their OUTPUT dim, qkv biases sliced with their columns,
        and (with ``moe_ep``) expert weights sharded on the expert axis.
        Ramp heads, the final head, embeddings, and every norm replicate —
        exit masks are computed identically on all devices, no round-trip."""
        tpx = axes.model
        specs = jax.tree.map(lambda i: P(), self.schema(), is_leaf=is_info)

        def fix_slot(slot: SlotSpec, sp, pfx):
            if slot.mixer == "attn":
                mx = sp["mixer"]
                for k in ("wq", "wk", "wv", "wo"):
                    mx[k] = P(*pfx, None, tpx)
                for k in ("bq", "bk", "bv"):
                    if k in mx:
                        mx[k] = P(*pfx, tpx)
            if slot.ffn == "dense":
                for k in ("w_gate", "w_up", "w_down"):
                    sp["ffn"][k] = P(*pfx, None, tpx)
            elif slot.ffn == "moe" and moe_ep:
                for k in ("w_gate", "w_up", "w_down"):
                    sp["ffn"][k] = P(*pfx, tpx, None, None)

        plan = self.plan
        for i, slot in enumerate(plan.prefix):
            fix_slot(slot, specs["prefix"][i], ())
        for s, slot in enumerate(plan.period):
            fix_slot(slot, specs["blocks"][s], (None,))
        for i, slot in enumerate(plan.suffix):
            fix_slot(slot, specs["suffix"][i], ())
        return specs

    def tp_cache_specs(self, cache, axes: MeshAxes, *, data_shard: bool = False):
        """Per-leaf shard_map specs for a decode cache under TP: every
        supported leaf is an attention k/v (contiguous ``(L?,B,S,K,hd)`` or
        paged ``(L?,P,bs,K,hd)``) with the kv-head axis at ``ndim-2`` —
        that axis shards over `model`, so per-device KV bytes are
        ``total / tp``. With ``data_shard`` (contiguous only) the batch
        axis (``ndim-4``) additionally shards over `data`."""

        def leaf(x):
            ent = [None] * x.ndim
            ent[x.ndim - 2] = axes.model
            if data_shard:
                ent[x.ndim - 4] = axes.d
            return P(*ent)

        return jax.tree.map(leaf, cache)

    def _mesh_degrees(self, mesh, axes: MeshAxes) -> Tuple[int, int]:
        m = mesh_axis_size(mesh, axes.model)
        dp = 1
        for a in axes.data:
            dp *= mesh_axis_size(mesh, a)
        return m, dp

    def decode_sharded(self, params, cache, tokens, pos, *, mesh,
                       axes=LY.TEST_AXES, active_sites=None, moe_impl="dense",
                       block_tables=None, exit_thresholds=None,
                       with_logits=False):
        """One decode step through ``shard_map`` on a ``(data, model)``
        mesh: tensor-parallel attention/MLP with the KV cache (contiguous
        or paged pool) sharded by kv head, bit-identical to single-device
        ``decode`` (see ``TpCtx``). Ramp heads, the final head, and the
        fused exit decision replicate, so exit masks never leave the
        device. Returns ``(new_cache, outs)`` with the cache left sharded."""
        m, dp = self._mesh_degrees(mesh, axes)
        paged = block_tables is not None
        tokens = jnp.asarray(tokens)
        self.tp_check(m, dp=dp, paged=paged, batch=tokens.shape[0])
        dsp = axes.d if dp > 1 else None
        pspecs = self.tp_param_specs(axes, moe_ep=(moe_impl == "ep"))
        cspecs = self.tp_cache_specs(cache, axes, data_shard=dp > 1)
        args = [params, cache, tokens, jnp.asarray(pos, jnp.int32)]
        specs = [pspecs, cspecs, P(dsp, None), P(dsp)]
        if paged:
            args.append(jnp.asarray(block_tables, jnp.int32))
            specs.append(P(dsp, None))
        if active_sites is not None:
            args.append(jnp.asarray(active_sites, jnp.int32))
            specs.append(P(None))
        if exit_thresholds is not None:
            args.append(jnp.asarray(exit_thresholds, jnp.float32))
            specs.append(P(None))
        outs_spec = {"final": P(dsp)}
        if active_sites is not None:
            outs_spec["ramps"] = P(None, dsp)
        ctx = TpCtx(m, partial(_tp_gather, axes.model),
                    axes.data if dp > 1 else None)

        def body(p, c, toks, po, *rest):
            it = iter(rest)
            tb = next(it) if paged else None
            act = next(it) if active_sites is not None else None
            thr = next(it) if exit_thresholds is not None else None
            return self.decode(
                p, c, toks, po, active_sites=act, axes=axes, mesh=None,
                moe_impl=moe_impl, block_tables=tb, exit_thresholds=thr,
                tp=ctx, with_logits=with_logits,
            )

        return shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=(cspecs, outs_spec),
                         check_vma=False)(*args)

    def decode_sharded_multi(self, params, cache, tokens, pos, n_steps, *,
                             mesh, n_max, axes=LY.TEST_AXES, active_sites=None,
                             thresholds=None, row_valid=None, moe_impl="dense",
                             block_tables=None):
        """``decode_multi`` through one ``shard_map``: the whole
        ``lax.while_loop`` window runs INSIDE the mapped body, so the
        PR 8 one-sync-per-window contract survives sharding — exit masks
        are evaluated on replicated ramp heads per device and the only
        host round-trip stays at the window boundary."""
        m, dp = self._mesh_degrees(mesh, axes)
        paged = block_tables is not None
        tokens = jnp.asarray(tokens)
        B = tokens.shape[0]
        self.tp_check(m, dp=dp, paged=paged, batch=B)
        dsp = axes.d if dp > 1 else None
        K = 0 if active_sites is None else int(jnp.shape(active_sites)[0])
        if row_valid is None:
            row_valid = jnp.ones((B,), bool)
        pspecs = self.tp_param_specs(axes, moe_ep=(moe_impl == "ep"))
        cspecs = self.tp_cache_specs(cache, axes, data_shard=dp > 1)
        args = [params, cache, tokens, jnp.asarray(pos, jnp.int32),
                jnp.asarray(n_steps, jnp.int32), jnp.asarray(row_valid, bool)]
        specs = [pspecs, cspecs, P(dsp, None), P(dsp), P(), P(dsp)]
        if paged:
            args.append(jnp.asarray(block_tables, jnp.int32))
            specs.append(P(dsp, None))
        if active_sites is not None:
            args.append(jnp.asarray(active_sites, jnp.int32))
            specs.append(P(None))
        if thresholds is not None:
            args.append(jnp.asarray(thresholds, jnp.float32))
            specs.append(P(None))
        rec_specs = (P(None, None, dsp), P(None, None, dsp),
                     P(None, dsp), P(None, dsp), P())
        ctx = TpCtx(m, partial(_tp_gather, axes.model),
                    axes.data if dp > 1 else None)

        def body(p, c, toks, po, n, valid, *rest):
            it = iter(rest)
            tb = next(it) if paged else None
            act = next(it) if active_sites is not None else None
            thr = next(it) if thresholds is not None else None
            return self.decode_multi(
                p, c, toks, po, n, n_max=n_max, active_sites=act,
                thresholds=thr, row_valid=valid, axes=axes, mesh=None,
                moe_impl=moe_impl, block_tables=tb, tp=ctx,
            )

        return shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=(cspecs, rec_specs),
                         check_vma=False)(*args)

    def _head_stats(self, params, h_last, pooled, active_sites,
                    axes=None, mesh=None, exit_thresholds=None,
                    with_logits=False):
        """Final + ramp confidence stats for serving. h_last: (B,1,d).

        With cfg.pallas_head != 'off', stats stream through the fused
        ramp_head kernel — (B,V) logits are never materialized in HBM.

        With ``exit_thresholds`` (K,) f32 (the device-resident threshold
        vector, aligned with ``active_sites``), the ramps output also
        carries ``exit`` (K,B) int32 — the per-ramp on-device exit
        decision ``(1 − maxprob) < threshold`` (strict, so 0.0 precludes
        exiting). On the pallas path the compare happens INSIDE the fused
        kernel (``ramp_head_exit``); the dense path applies the identical
        f32 formula, so the two agree bit-for-bit with the host's
        ``simulate_exits``."""
        cfg = self.cfg
        h = LY.apply_norm(cfg, params["final_norm"], h_last)
        if cfg.pallas_head != "off" and not with_logits:
            return self._head_stats_pallas(params, h, pooled, active_sites,
                                           exit_thresholds=exit_thresholds)
        logits = LY.unembed(cfg, params["tok"], h)[:, 0].astype(jnp.float32)
        if axes is not None:
            logits = LY.constrain(logits, axes.aspec("data", "model"), mesh)
        logits = _mask_pad_vocab(cfg, logits)
        outs = {"final": _stats(logits)}
        if with_logits:
            outs["final"]["logits"] = logits
        if active_sites is not None:
            rl = self.ramp_outputs(params, pooled, site_idx=active_sites,
                                   axes=axes, mesh=mesh)
            rl = _mask_pad_vocab(cfg, rl[:, :, 0])  # (K,B,V)
            outs["ramps"] = _stats(rl)
            if exit_thresholds is not None:
                thr = jnp.asarray(exit_thresholds, jnp.float32)
                unc = 1.0 - outs["ramps"]["maxprob"].astype(jnp.float32)
                outs["ramps"]["exit"] = (unc < thr[:, None]).astype(jnp.int32)
        return outs

    def _head_stats_pallas(self, params, h_normed, pooled, active_sites,
                           exit_thresholds=None):
        from repro.kernels.ramp_head import (
            ramp_head_exit,
            ramp_head_stats,
            stats_to_confidence,
        )

        cfg = self.cfg
        interp = cfg.pallas_head == "interpret"
        wf = params["tok"]["embed"].T if cfg.tie_embeddings else params["tok"]["lm_head"]

        def stats_of(hb, w, thr=None):
            kw = dict(interpret=interp, v_limit=cfg.vocab_size,
                      block_b=min(8, hb.shape[0]), block_v=min(1024, w.shape[1]))
            if thr is None:
                m, s, t, idx = ramp_head_stats(hb, w, **kw)
                mask = None
            else:
                m, s, t, idx, mask = ramp_head_exit(hb, w, thr, **kw)
            label, maxprob, entropy, _ = stats_to_confidence(m, s, t, idx)
            out = {"label": label, "maxprob": maxprob, "entropy": entropy}
            if mask is not None:
                out["exit"] = mask
            return out

        outs = {"final": stats_of(h_normed[:, 0], wf)}
        if active_sites is not None:
            site_idx = jnp.asarray(active_sites, jnp.int32)
            sites = jnp.asarray(self.sites, jnp.int32)
            hs = jnp.take(pooled, jnp.take(sites, site_idx), axis=0)[:, :, 0]  # (K,B,d)
            nw = jnp.take(params["ramps"]["norm_w"], site_idx, axis=0)
            hs = LY.rms_norm(hs, nw[:, None, :])
            K = hs.shape[0]
            B = hs.shape[1]
            per = []
            for kk in range(K):  # K is small & static (ramp budget slots)
                w = wf if cfg.ramp_style == "tied" else jnp.take(
                    params["ramps"]["head"], site_idx[kk], axis=0
                )
                thr = (jnp.broadcast_to(
                    jnp.asarray(exit_thresholds, jnp.float32)[kk], (B,))
                    if exit_thresholds is not None else None)
                per.append(stats_of(hs[kk], w, thr))
            outs["ramps"] = {
                key: jnp.stack([p[key] for p in per]) for key in per[0]
            }
        return outs


def _stats(logits):
    """logits: (..., V) f32 -> {label, maxprob, entropy} (paper's ~1KB
    per-ramp record: top-1 result + error score)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    label = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    maxprob = jnp.exp(jnp.max(logits, axis=-1) - lse)
    p = jax.nn.softmax(logits, axis=-1)
    plogp = jnp.where(p > 0, p * jnp.log(jnp.clip(p, 1e-30)), 0.0)
    entropy = -jnp.sum(plogp, axis=-1)
    return {"label": label, "maxprob": maxprob, "entropy": entropy}


def _mask_pad_vocab(cfg, logits):
    """Sharding-friendly pad-vocab mask (no concat/gather: keeps the vocab
    dim sharded over `model` with zero resharding)."""
    V = cfg.vocab_size
    if logits.shape[-1] == V:
        return logits
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    return jnp.where(col < V, logits, -1e30)


def _masked_ce(cfg, logits, labels):
    """Cross-entropy with -1 padding labels and padded-vocab masking.
    The label log-prob is extracted with an iota/where reduction rather than
    take_along_axis — a vocab-sharded gather would all-gather full logits
    (hundreds of GB at train_4k scale); the reduction psums a scalar."""
    logits = logits.astype(jnp.float32)
    V, Vp = cfg.vocab_size, logits.shape[-1]
    if Vp > V:
        logits = _mask_pad_vocab(cfg, logits)
    valid = labels >= 0
    lab = jnp.clip(labels, 0)
    m = jnp.max(logits, axis=-1)
    lse = m + jnp.log(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1))
    col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    ll = jnp.sum(jnp.where(col == lab[..., None], logits, 0.0), axis=-1)
    nll = (lse - ll) * valid
    return jnp.sum(nll) / jnp.clip(jnp.sum(valid), 1)


def _cache_len(cache) -> int:
    # attn caches have shape (..., B, S, K, hd); mla (..., B, S, r).
    # With windowed local caches present, the GLOBAL (longest) length is the
    # decode mask length -> take the max across leaves.
    found: List[int] = []

    def _find(c):
        if isinstance(c, dict):
            if "k" in c and hasattr(c["k"], "shape"):
                found.append(c["k"].shape[-3])
            if "c" in c and hasattr(c["c"], "shape"):
                found.append(c["c"].shape[-2])
            # skip cross-attn memory ("xkv"): its M tokens are attended
            # unmasked and must not define the self-attn decode mask length
            for key, v in c.items():
                if key not in ("k", "v", "c", "k_pe", "xkv"):
                    _find(v)
        elif isinstance(c, (list, tuple)):
            for v in c:
                _find(v)

    _find(cache)
    if not found:
        raise ValueError("cache has no attention leaves; decode mask undefined")
    return max(found)
