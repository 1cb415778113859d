"""The benchmark's traffic: every input a cell feeds the system, made from
``--seed`` on the device.

They follow the program's own generator (``repro.data.synthetic``
``make_lm_data``), and are kept here so that no change to the program can
move the yardstick's inputs. One difference is deliberate, to keep set-up
short: :func:`lm_corpus` draws a client's sequences as a batch of
independent walks on the client's bigram chain, where ``make_lm_data`` walks
one long stream (a million sequential steps for 2048 x 513 tokens, against
513).
"""
from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp

#: the chain of domain d is keyed by ``CHAIN_SEED + d``, never by the seed
CHAIN_SEED = 7_000_000


def seed_key(seed: int):
    """A PRNG key from any non-negative seed: the low 32 bits seed the key
    and the high bits are folded in (``PRNGKey`` alone drops them)."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def lm_corpus(key, n_seqs: int, seq_plus_one: int, vocab: int, *,
              domain: int, order_sharpness: float = 4.0) -> jnp.ndarray:
    """``int32[n_seqs, seq_plus_one]``: independent walks on the bigram
    chain of ``domain`` (``make_lm_data``'s chain), each from a
    uniform first token."""
    kt = jax.random.PRNGKey(CHAIN_SEED + domain)
    ks = jax.random.fold_in(key, domain)
    logits = order_sharpness * jax.random.normal(kt, (vocab, vocab))
    k0, kw = jax.random.split(ks)
    first = jax.random.randint(k0, (n_seqs,), 0, vocab)

    def step(tok, k):
        nxt = jax.random.categorical(k, logits[tok], axis=-1)
        return nxt, nxt

    _, rest = jax.lax.scan(step, first,
                           jax.random.split(kw, seq_plus_one - 1))
    return jnp.concatenate([first[None], rest]).T.astype(jnp.int32)


def lm_federation(key, clients: int, n_seqs: int, seq: int, vocab: int,
                  n_test: int) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Per-client corpora (client k walks chain k) and a test set mixing
    every client's chain, ``n_test`` sequences in all."""
    gen = jax.jit(lm_corpus, static_argnums=(1, 2, 3))
    data = [gen(jax.random.fold_in(key, 100 + k), n_seqs, seq + 1, vocab,
                domain=k) for k in range(clients)]
    per = max(1, n_test // clients)
    test = jnp.concatenate([
        gen(jax.random.fold_in(key, 999 + k), per, seq + 1, vocab, domain=k)
        for k in range(clients)])
    return data, test
