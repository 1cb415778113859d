"""Plain reference of the decoder that the LM configurations run: Phi-3's
layer as published (pre-norm, RMSNorm, multi-head attention with RoPE,
SwiGLU MLP, a final RMSNorm and an output projection, tied or untied), in
straightforward ``jax.numpy`` with no kernels, remat or chunking.

Two departures follow the program, so that the two can be compared on the
same weights: RoPE rotates interleaved pairs of a head's features (the
published model rotates its two halves, which is the same up to a fixed
permutation of the query and key columns), and RMSNorm's eps is the
program's 1e-6 (the configuration lists it under ``reduced``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def layout(m: Dict, dtype=jnp.float32):
    """The parameter tree of one decoder, as the program lays it out: the
    layers stacked on a leading axis under ``stack[0]``."""
    d, L = m["hidden_size"], m["num_hidden_layers"]
    q = m["num_attention_heads"] * m["head_dim"]
    kv = m["num_key_value_heads"] * m["head_dim"]
    f, V = m["intermediate_size"], m["vocab_size"]

    def S(*shape):
        return jax.ShapeDtypeStruct(shape, dtype)

    layer = {"norm1": {"g": S(L, d)},
             "mixer": {"wq": {"w": S(L, d, q)}, "wk": {"w": S(L, d, kv)},
                       "wv": {"w": S(L, d, kv)}, "wo": {"w": S(L, q, d)}},
             "norm2": {"g": S(L, d)},
             "ffn": {"gate": {"w": S(L, d, f)}, "up": {"w": S(L, d, f)},
                     "down": {"w": S(L, f, d)}}}
    tree = {"embed": {"e": S(V, d)}, "prefix": (), "stack": (layer,),
            "tail": (), "norm_f": {"g": S(d)}}
    if not m["tie_word_embeddings"]:
        tree["head"] = {"w": S(d, V)}
    return tree


def rule(m: Dict):
    """Gains of 1, every matrix N(0, initializer_range^2)."""
    std = m["initializer_range"]

    def r(path, shape):
        return ("ones",) if path[-1] == "g" else ("normal", std)
    return r


def rmsnorm(x, g, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype) * g


def rope(x, theta):
    """x: [B, S, H, D]; interleaved pairs (see the module docstring)."""
    S, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def attention(q, k, v):
    """Causal softmax attention, scores and weights in float32."""
    S = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def forward(p, m: Dict, tokens):
    """Logits ``[B, S, V]`` in float32."""
    H, KV, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    x = p["embed"]["e"][tokens]
    B, S, _ = x.shape
    layers = p["stack"][0]
    for i in range(m["num_hidden_layers"]):
        lp = jax.tree_util.tree_map(lambda a: a[i], layers)
        h = rmsnorm(x, lp["norm1"]["g"], eps)
        q = rope((h @ lp["mixer"]["wq"]["w"]).reshape(B, S, H, hd), theta)
        k = rope((h @ lp["mixer"]["wk"]["w"]).reshape(B, S, KV, hd), theta)
        v = (h @ lp["mixer"]["wv"]["w"]).reshape(B, S, KV, hd)
        if KV != H:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        x = x + attention(q, k, v).reshape(B, S, H * hd) @ lp["mixer"]["wo"]["w"]
        h = rmsnorm(x, lp["norm2"]["g"], eps)
        ff = lp["ffn"]
        x = x + (jax.nn.silu(h @ ff["gate"]["w"]) * (h @ ff["up"]["w"])
                 ) @ ff["down"]["w"]
    x = rmsnorm(x, p["norm_f"]["g"], eps)
    head = p["head"]["w"] if "head" in p else p["embed"]["e"].T
    return (x @ head).astype(jnp.float32)


def sample(toks, idx):
    """Inputs and next-token labels of the sequences ``idx``."""
    return toks[idx, :-1], toks[idx, 1:]


def n_examples(toks) -> int:
    return int(toks.shape[0])


def batch_size(fed: Dict, traffic: Dict) -> int:
    return int(traffic["batch"])
