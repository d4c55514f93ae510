"""Plain reference of the Qwen2 decoder (Qwen2 and Qwen1.5 share it) with
Apparate's ramp heads, in float32 at full matmul precision.

Follows the published architecture (Qwen2 technical report,
arXiv:2407.10671; the Hugging Face ``Qwen2ForCausalLM``): token
embedding; per layer RMSNorm, GQA self-attention with biases on q/k/v and
rotary embeddings over the whole head (rotate-half), a residual, RMSNorm,
a SwiGLU MLP and a residual; a final RMSNorm and the output head (the
embedding, transposed, where the embeddings are tied). A ramp at site
``s`` reads the residual stream after layer ``s``, applies its own
RMSNorm and a full-vocabulary head (its own, or the output head for
``tied`` ramps). Departures from the published model: none in the
arithmetic; the weights are random, read in the benchmark's layout
(``arch/qwen2.py``), where a norm gain is stored as ``gain - 1``.

Nothing here imports the program. The forward pass runs one layer per
call, so it fits beside the served weights, and it sees the whole
sequence at once: no cache, no batching, no kernels.

``quant`` makes the control: every matrix rounded per output column to
``int8`` or ``fp8`` (e4m3) and back, the rest unchanged.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EPS = 1e-6


def quantize(w, quant, axis):
    """Round ``w`` (f32) to ``quant`` per slice along ``axis`` (the input
    dimension: one scale per output column) and return it in f32."""
    if quant is None:
        return w
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if quant == "int8":
        s = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(w / s), -127, 127) * s
    if quant == "fp8":
        s = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (w / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def rms(x, stored_gain):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + EPS)
    return y * (1.0 + stored_gain)


def rope(x, positions, theta):
    """x: (T, n, hd); rotate-half over the whole head dimension."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    s, c = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


@functools.partial(jax.jit, static_argnames=("H", "K", "theta", "quant"))
def layer(h, blocks, li, *, H, K, theta, quant):
    """One decoder layer over the whole sequence. h: (T, d) f32."""
    w = jax.tree.map(lambda x: jax.lax.dynamic_index_in_dim(x, li, keepdims=False)
                     .astype(jnp.float32), blocks)
    mx, ffn = w["mixer"], w["ffn"]
    T, d = h.shape
    hd = mx["wq"].shape[1] // H
    G = H // K
    pos = jnp.arange(T)
    x = rms(h, w["ln1"]["w"])
    q = jnp.dot(x, quantize(mx["wq"], quant, 0), precision=HI) + mx["bq"]
    k = jnp.dot(x, quantize(mx["wk"], quant, 0), precision=HI) + mx["bk"]
    v = jnp.dot(x, quantize(mx["wv"], quant, 0), precision=HI) + mx["bv"]
    q = rope(q.reshape(T, H, hd), pos, theta).reshape(T, K, G, hd)
    k = rope(k.reshape(T, K, hd), pos, theta)
    v = v.reshape(T, K, hd)
    s = jnp.einsum("tkgd,skd->kgts", q, k, precision=HI) / math.sqrt(hd)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgts,skd->tkgd", p, v, precision=HI).reshape(T, H * hd)
    h = h + jnp.dot(o, quantize(mx["wo"], quant, 0), precision=HI)
    x = rms(h, w["ln2"]["w"])
    g = jnp.dot(x, quantize(ffn["w_gate"], quant, 0), precision=HI)
    u = jnp.dot(x, quantize(ffn["w_up"], quant, 0), precision=HI)
    return h + jnp.dot(jax.nn.silu(g) * u, quantize(ffn["w_down"], quant, 0), precision=HI)


@functools.partial(jax.jit, static_argnames=("vocab", "quant", "transposed"))
def head_logits(h, gain, w, *, vocab, quant, transposed):
    """Logits (n, vocab) of hidden states h (n, d) under a norm and a
    (d, Vp) head (given as the (Vp, d) embedding where ``transposed``)."""
    w = w.T if transposed else w
    w = quantize(w[:, :vocab].astype(jnp.float32), quant, 0)
    return jnp.dot(rms(h, gain), w, precision=HI)


@functools.partial(jax.jit, static_argnames=("quant",))
def embed(table, tokens, *, quant):
    """Embedding rows, each rounded with its own scale in the control."""
    return quantize(table[tokens].astype(jnp.float32), quant, 1)


class Reference:
    """The reference over one weight tree (the benchmark's draw)."""

    def __init__(self, conf: dict, weights, sites, *, seq_len: int, n_pos: int):
        """Every set of positions is padded to ``n_pos``, and every sequence
        to the first of a few lengths up to ``seq_len`` that holds it (later
        tokens cannot change a causal model's earlier positions), so a
        handful of programs serves every request."""
        m = conf["model"]
        self.H, self.K = m["num_attention_heads"], m["num_key_value_heads"]
        self.L, self.V = m["num_hidden_layers"], m["vocab_size"]
        self.theta = float(m["rope_theta"])
        self.tied = bool(m["tie_word_embeddings"])
        self.ramp_style = conf["ramp_style"]
        self.w, self.sites = weights, tuple(sites)
        self.lengths = sorted({min(int(seq_len), 512 * 2**k) for k in range(8)})
        self.n_pos = int(n_pos)

    def _out_head(self):
        tok = self.w["tok"]
        return (tok["embed"], True) if self.tied else (tok["lm_head"], False)

    def _ramp_head(self, j):
        if self.ramp_style == "tied":
            return self._out_head()
        return self.w["ramps"]["head"][j], False

    def hidden(self, seq, positions, quant=None):
        """Final hidden states at ``positions`` and each ramp site's."""
        T, n = len(seq), len(positions)
        if T > self.lengths[-1] or n > self.n_pos:
            raise ValueError(f"{T} tokens at {n} positions exceed {self.lengths[-1]} / {self.n_pos}")
        toks = np.zeros(next(L for L in self.lengths if L >= T), np.int32)
        toks[:T] = seq
        pos = np.zeros(self.n_pos, np.int32)
        pos[:n] = positions
        h = embed(self.w["tok"]["embed"], jnp.asarray(toks), quant=quant)
        pos = jnp.asarray(pos)
        at_site = {}
        for li in range(self.L):
            h = layer(h, self.w["blocks"][0], jnp.int32(li), H=self.H, K=self.K,
                      theta=self.theta, quant=quant)
            if li in self.sites:
                at_site[self.sites.index(li)] = h[pos]
        return h[pos], at_site

    def logits(self, seq, positions, ramp_sites_used, quant=None):
        """Final-head logits (n, V) at ``positions`` and, for each ramp
        index in ``ramp_sites_used``, that ramp's logits (n, V)."""
        n = len(positions)
        hf, hs = self.hidden(seq, positions, quant)
        w, tr = self._out_head()
        final = head_logits(hf, self.w["final_norm"]["w"], w, vocab=self.V,
                            quant=quant, transposed=tr)[:n]
        ramps = {}
        for j in ramp_sites_used:
            w, tr = self._ramp_head(j)
            ramps[j] = head_logits(hs[j], self.w["ramps"]["norm_w"][j], w,
                                   vocab=self.V, quant=quant, transposed=tr)[:n]
        return final, ramps
