"""What the harness drives: one federation of the program, built from a
configuration file and a traffic file, with its state made from the seed.

A family module (``bench/family_<family>.py``) builds the program's engine,
its data and its block-edge host work; this base class holds what every
family shares: the state made from the benchmark's weights, the round-block
the window calls, and the numbers the correctness check reads off the state.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_fed, traffic, weights

ROLES = ("private", "proxy")


def run_keys(seed: int) -> Dict[str, jnp.ndarray]:
    """The keys of one run: data, weights and the engine's base key."""
    root = traffic.seed_key(seed)
    return {name: jax.random.fold_in(root, i)
            for i, name in enumerate(("data", "weights", "run"))}


class Federation:
    """A family's constructor sets ``engine``, ``models`` (the private and
    proxy model in the reference's terms), ``fed`` (the configuration's
    federation block), ``traffic``, ``rounds_per_block`` and
    ``local_steps``; its ``setup()`` makes ``data`` and calls
    :meth:`make_state`. ``reference`` is the family's reference module."""

    reference = None
    mesh = None

    def __init__(self, seed: int):
        self.seed = seed
        self.keys = run_keys(seed)
        self.t = 0
        self.state = None
        self.data: List = []

    # -- state -------------------------------------------------------------

    def layouts(self):
        return {r: self.reference.layout(self.models[r]) for r in ROLES}

    def make_state(self) -> None:
        """The engine's stacked state, made from the weight key in one
        jitted call: weights by the reference's rule, Adam moments 0, PushSum
        weights 1. Refuses a program whose parameter tree differs from the
        layout the reference reads."""
        K = self.fed["clients"]
        one = jax.eval_shape(self.engine.init_fns[0], self.keys["run"])
        lays = self.layouts()
        for r in ROLES:
            if set(one[r]) != {"params", "opt"} or not weights.same_layout(
                    one[r]["params"], lays[r]):
                raise ValueError(
                    f"the program's {r} state {weights.describe(one[r])} is "
                    f"not the layout the reference reads "
                    f"{weights.describe(lays[r])}")
        rules = {r: self.reference.rule(self.models[r]) for r in ROLES}

        def client(wkey, k):
            st = {}
            for name, sub in one.items():
                if name in ROLES:
                    st[name] = {
                        "params": weights.make_params(wkey, k, name,
                                                      lays[name],
                                                      rules[name]),
                        "opt": jax.tree_util.tree_map(
                            lambda s: jnp.zeros(s.shape, s.dtype),
                            sub["opt"])}
                elif name == "w":
                    st[name] = jnp.ones(sub.shape, sub.dtype)
                else:
                    st[name] = jax.tree_util.tree_map(
                        lambda s: jnp.zeros(s.shape, s.dtype), sub)
            return st

        def make(wkey):
            return jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs),
                *[client(wkey, k) for k in range(K)])

        shardings = None
        if self.mesh is not None:
            shardings = jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec(self.engine.axis))
        self.state = jax.jit(make, out_shardings=shardings)(
            self.keys["weights"])

    # -- the timed path ----------------------------------------------------

    def run_block(self) -> Dict[str, np.ndarray]:
        """One round-block through the engine's own call, waited on."""
        self.state, metrics = self.engine.run_rounds(
            self.state, self.data, self.t, self.rounds_per_block,
            self.keys["run"])
        jax.block_until_ready(self.state)
        self.t += self.rounds_per_block
        return metrics

    def edge(self, metrics: Dict[str, np.ndarray]) -> None:
        """The host work the program's own driver does at a block edge."""

    def rounds_failed(self, metrics: Dict[str, np.ndarray]) -> int:
        """Rounds of a block in which some client's loss is not finite."""
        bad = np.zeros(self.rounds_per_block, bool)
        for v in metrics.values():
            bad |= ~np.isfinite(np.asarray(v)).all(axis=1)
        return int(bad.sum())

    # -- what the check reads ----------------------------------------------

    def losses(self, metrics) -> np.ndarray:
        """[rounds, K, 2]: private then proxy loss of each round's last
        local step."""
        return np.stack([np.asarray(metrics["private_loss"]),
                         np.asarray(metrics["proxy_loss"])], axis=-1)

    def moment_norms(self) -> Dict[str, np.ndarray]:
        """{role: [K, leaves]} norms of Adam's first moment."""
        fn = jax.jit(lambda m: [jnp.sqrt(jnp.sum(
            jnp.square(x.astype(jnp.float32)),
            axis=tuple(range(1, x.ndim)))) for x in
            jax.tree_util.tree_leaves(m)])
        return {r: np.stack([np.asarray(v) for v in fn(
            self.state[r]["opt"].m)], axis=1) for r in ROLES}

    def change_norms(self) -> Dict[str, np.ndarray]:
        """{role: [K, leaves]} norms of each weight's change since the
        weights were made."""
        lays = self.layouts()
        out = {}
        for r in ROLES:
            rule = self.reference.rule(self.models[r])

            def fn(params, wkey, k, r=r, rule=rule):
                mine = jax.tree_util.tree_map(lambda x: x[k], params)
                init = weights.make_params(wkey, k, r, lays[r], rule)
                return [jnp.sqrt(jnp.sum(jnp.square(
                    a.astype(jnp.float32) - b.astype(jnp.float32))))
                    for a, b in zip(jax.tree_util.tree_leaves(mine),
                                    jax.tree_util.tree_leaves(init))]

            jf = jax.jit(fn)
            out[r] = np.stack([
                np.asarray([float(v) for v in jf(
                    self.state[r]["params"], self.keys["weights"],
                    jnp.int32(k))])
                for k in range(self.fed["clients"])])
        return out

    def free(self) -> None:
        """Drop the program's state and data from the device."""
        self.state = None
        self.data = []

    def follow(self, blocks: int, dtype=jnp.float32) -> Dict:
        """The reference over the first ``blocks`` blocks of this run, on
        the run's inputs made again from the seed."""
        return reference_fed.follow(
            self.reference, self.models, self.fed, self.traffic,
            self.ref_data(), self.keys["weights"], self.keys["run"], blocks,
            self.local_steps, dtype)

    def ref_data(self) -> List:
        """The run's inputs, made again from the seed for the reference."""
        raise NotImplementedError
