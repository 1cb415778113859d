"""Faults a training cell can have, planted in the program's step: used by
the CPU tests that see a broken run come out not correct, and by
``calibrate.py`` to read each fault on the chip. Each takes the engine's
``step(state, batch, key)`` and returns a broken one."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def frozen(step):
    """A step that returns its state unchanged."""
    def f(state, batch, key):
        _, m = step(state, batch, key)
        return state, m
    return f


def _first_half_twice(batch):
    def h(x):
        n = x.shape[0] // 2
        return jnp.concatenate([x[:n], x[:n]])
    return jax.tree_util.tree_map(h, batch)


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    return lambda state, batch, key: step(state, _first_half_twice(batch), key)


def _alter_one_label(batch):
    y = batch["labels"]
    return dict(batch, labels=y.at[0, 0].set((y[0, 0] + 1) % 2))


def token(step):
    """One label altered where the batch is drawn (to the other of labels
    0 and 1, so it stays in range for any vocabulary)."""
    return lambda state, batch, key: step(state, _alter_one_label(batch), key)


FAULTS = {"frozen": frozen, "half_batch": half_batch, "token": token}


def plant(fed, name: str):
    """Break ``fed``'s engine step by the fault ``name``; returns ``fed``."""
    step = FAULTS[name](fed.engine.step_fns[0])
    fed.engine.step_fns = [step] * fed.engine.K
    return fed
