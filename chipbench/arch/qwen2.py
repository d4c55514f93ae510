"""The Qwen2 decoder (Qwen2 and Qwen1.5 share it) as the benchmark draws
and counts it: its sizes, its parameter layout, the weights drawn on the
device from the seed, and the operations of a decoded token.

A configuration names its architecture (``"architecture": "qwen2"``);
the harness loads this file and ``reference/qwen2.py`` by that name, so
another architecture is two files beside these and no edit.

Storage conventions the reference reads (``reference/qwen2.py``):
  * matrices are ``x @ W`` with W of shape (in, out); per-layer leaves are
    stacked on a leading layer axis;
  * an RMSNorm gain is stored as ``gain - 1`` (float32);
  * ``ramps.head[j]`` is the full-vocabulary head of ramp site ``j``;
    with ``ramp_style == "tied"`` the ramps use the output head instead.

Distributions (the configuration's ``"weights"``): normal(0, ``std``)
for projections, embeddings and biases, ``std / sqrt(2 * layers)`` for
the two output projections; norm gains ``1 + normal(0, norm_noise)``; the
final and ramp norms carry ``head_gain`` so the softmax is peaked; each fc
ramp head is the output head plus its own normal(0, ``ramp_noise * std``),
so no two ramps share a head. These are random weights: the residual
stream at a ramp's site is not the final one, so a ramp seldom agrees
with the final head, and at 99% agreement no token exits.
"""
from __future__ import annotations

import math
from typing import Dict

from harness.weights import nest, padded_vocab, ramp_sites


def dims(conf: dict) -> Dict[str, int]:
    m = conf["model"]
    d, H = m["hidden_size"], m["num_attention_heads"]
    return dict(
        d=d, H=H, K=m["num_key_value_heads"], hd=m.get("head_dim", d // H),
        F=m["intermediate_size"], V=m["vocab_size"], Vp=padded_vocab(m["vocab_size"]),
        L=m["num_hidden_layers"], S=len(ramp_sites(m["num_hidden_layers"])),
        tied=bool(m["tie_word_embeddings"]),
    )


def layout(conf: dict) -> dict:
    """``{path: (shape, dtype)}`` of every leaf, in the program's tree."""
    g = dims(conf)
    d, H, K, hd, F, Vp, L, S = (g[k] for k in ("d", "H", "K", "hd", "F", "Vp", "L", "S"))
    dt = conf["model"]["torch_dtype"]
    out = {
        ("tok", "embed"): ((Vp, d), dt),
        ("blocks", 0, "ln1", "w"): ((L, d), "float32"),
        ("blocks", 0, "mixer", "wq"): ((L, d, H * hd), dt),
        ("blocks", 0, "mixer", "wk"): ((L, d, K * hd), dt),
        ("blocks", 0, "mixer", "wv"): ((L, d, K * hd), dt),
        ("blocks", 0, "mixer", "wo"): ((L, H * hd, d), dt),
        ("blocks", 0, "mixer", "bq"): ((L, H * hd), dt),
        ("blocks", 0, "mixer", "bk"): ((L, K * hd), dt),
        ("blocks", 0, "mixer", "bv"): ((L, K * hd), dt),
        ("blocks", 0, "ln2", "w"): ((L, d), "float32"),
        ("blocks", 0, "ffn", "w_gate"): ((L, d, F), dt),
        ("blocks", 0, "ffn", "w_up"): ((L, d, F), dt),
        ("blocks", 0, "ffn", "w_down"): ((L, F, d), dt),
        ("final_norm", "w"): ((d,), "float32"),
        ("ramps", "norm_w"): ((S, d), "float32"),
    }
    if not g["tied"]:
        out[("tok", "lm_head")] = ((d, Vp), dt)
    if conf["ramp_style"] == "fc":
        out[("ramps", "head")] = ((S, d, Vp), dt)
    return out


def draw(conf: dict, key, out_shardings=None):
    """All weights from ``key`` in one jitted program, in their served dtype."""
    import jax
    import jax.numpy as jnp

    w = conf["weights"]
    std, g = float(w["std"]), dims(conf)
    out_std = std / math.sqrt(2 * g["L"])
    spec = layout(conf)
    paths = sorted(spec, key=str)

    def gen(key):
        keys = dict(zip(paths, jax.random.split(key, len(paths))))
        flat = {}
        for p in paths:
            shape, dt = spec[p]
            name = p[-1] if p[-1] != "w" else p[-2]
            if name in ("ln1", "ln2"):
                flat[p] = jax.random.normal(keys[p], shape, jnp.float32) * w["norm_noise"]
            elif name in ("final_norm", "norm_w"):
                flat[p] = (w["head_gain"] - 1.0) + jax.random.normal(
                    keys[p], shape, jnp.float32) * w["norm_noise"]
            elif name == "head":
                continue  # after the output head
            else:
                s = out_std if name in ("wo", "w_down") else std
                flat[p] = (jax.random.normal(keys[p], shape, jnp.float32) * s).astype(dt)
        if ("ramps", "head") in spec:
            p = ("ramps", "head")
            base = (flat[("tok", "embed")].T if g["tied"] else flat[("tok", "lm_head")])
            ks = jax.random.split(keys[p], g["S"])
            flat[p] = jnp.stack([
                (base.astype(jnp.float32)
                 + jax.random.normal(k, base.shape, jnp.float32) * (w["ramp_noise"] * std)
                 ).astype(spec[p][1]) for k in ks])
        return nest(flat)

    return jax.jit(gen, out_shardings=out_shardings)(key)


def layer_matmul_flops(g: Dict[str, int]) -> int:
    """Operations of one token through one layer's projections and MLP."""
    d, H, K, hd, F = g["d"], g["H"], g["K"], g["hd"], g["F"]
    return 2 * (d * (H + 2 * K) * hd + H * hd * d + 3 * d * F)


def decode_flops(g: Dict[str, int], row_steps: int, ctx_row_steps: int) -> int:
    """Backbone and final head of ``row_steps`` decoded tokens whose
    attended positions add up to ``ctx_row_steps`` (ramp heads excluded):
    every layer's projections, MLP and attention, then the output head."""
    from harness import costs

    att, _ = costs.paged_attention(g, ctx_row_steps, row_steps)
    return g["L"] * (row_steps * layer_matmul_flops(g) + att) + costs.head(g, row_steps)[0]
