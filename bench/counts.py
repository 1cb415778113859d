"""Operations and bytes that a round requires, computed from sizes.

The benchmark keeps its own count so that no change to the program can move
it. What counts (the ``round_mfu`` rule): one forward and one backward pass
of the private model and of the proxy over every example a round trains on.
The peer logits of the distillation loss come from those same forwards;
rematerialised forwards, recomputed peer logits and evaluation do not count.
A per-example gradient counts what a batched gradient would. A backward pass
is twice its forward. Attention counts its causal half.
"""
from __future__ import annotations

from typing import Dict


def lm_forward_flops_per_token(d: int, n_layers: int, heads: int,
                               kv_heads: int, head_dim: int, d_ff: int,
                               vocab: int, seq: int) -> float:
    """Forward FLOPs per token of a pre-norm decoder with SwiGLU and an
    output projection to ``vocab``: 2 per multiply-add of every matmul,
    plus causal attention over ``seq`` positions (a token attends to
    ``(seq + 1) / 2`` positions on average)."""
    q_out, kv_out = heads * head_dim, kv_heads * head_dim
    per_layer = 2 * (d * q_out + 2 * d * kv_out + q_out * d + 3 * d * d_ff)
    attn = 2 * 2 * heads * head_dim * (seq + 1) / 2
    return n_layers * (per_layer + attn) + 2 * d * vocab


def lm_model_flops(m: Dict, seq: int) -> float:
    """:func:`lm_forward_flops_per_token` of a config dict in the keys of
    the configuration files."""
    return lm_forward_flops_per_token(
        m["hidden_size"], m["num_hidden_layers"], m["num_attention_heads"],
        m["num_key_value_heads"], m["head_dim"], m["intermediate_size"],
        m["vocab_size"], seq)


def lm_round_flops(private: Dict, proxy: Dict, clients: int, local_steps: int,
                   batch: int, seq: int) -> float:
    """Required training FLOPs of one LM round: forward + backward (3x the
    forward) of both models over every trained token."""
    tokens = clients * local_steps * batch * seq
    return 3.0 * tokens * (lm_model_flops(private, seq)
                           + lm_model_flops(proxy, seq))
