"""Plain reference of the ProxyFL protocol (arXiv:2111.11343, Algorithm 1),
written from the paper in straightforward ``jax.numpy``: one client at a
time, float32 at the highest matmul precision, no kernels, remat or batching
over clients. The models come from a family module such as ``reference_lm``.

Per local step a client draws a batch, updates its private model on
``(1-alpha)*CE + alpha*KL(own || proxy)`` and its proxy on
``(1-beta)*CE + beta*KL(own || private)``, both from the step's starting
weights, the proxy by DP-SGD (every example's gradient clipped to ``C``,
Gaussian noise of ``sigma*C`` added to the sum, divided by the batch), each
by Adam with L2 weight decay. After the local steps the proxies are mixed by
PushSum over the exponential graph, ``z' = (P z) / (P w)``, ``w' = P w``.

The random schedule is the protocol's: round ``t`` runs under
``fold_in(run_key, 10000 + t)``, client ``k`` under ``fold_in(round_key, k)``,
and every local step splits its client key into (next, batch, noise); the
batch is ``randint(batch_key, (B,), 0, n)`` and the noise of leaf ``i`` is
``normal(split(noise_key, n_leaves)[i])``.

``dtype`` bfloat16 gives the control: the same reference with weights,
activations, gradients and moments in bfloat16.
"""
from __future__ import annotations

from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from . import weights

ROUND_KEY_OFFSET = 10_000


def cross_entropy(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def kl(p_logits, q_logits):
    lp = jax.nn.log_softmax(p_logits, axis=-1)
    lq = jax.nn.log_softmax(q_logits, axis=-1)
    return jnp.mean(jnp.sum(jnp.exp(lp) * (lp - lq), axis=-1))


def dml(own, peer, labels, a):
    return (1.0 - a) * cross_entropy(own, labels) + a * kl(
        own, jax.lax.stop_gradient(peer))


def adam(p, g, m, v, t, fed: Dict):
    """One Adam step with L2 weight decay; ``t`` is the step after it.
    Arithmetic in float32, results stored in the weights' dtype."""
    b1, b2, eps, lr, wd = 0.9, 0.999, 1e-8, fed["lr"], fed["weight_decay"]
    tf = t.astype(jnp.float32)
    c1, c2 = 1 - b1 ** tf, 1 - b2 ** tf
    leaves, treedef = jax.tree_util.tree_flatten(p)
    outs = ([], [], [])
    for p_, g_, m_, v_ in zip(leaves, jax.tree_util.tree_leaves(g),
                              jax.tree_util.tree_leaves(m),
                              jax.tree_util.tree_leaves(v)):
        p32 = p_.astype(jnp.float32)
        g32 = g_.astype(jnp.float32) + wd * p32
        m2 = b1 * m_.astype(jnp.float32) + (1 - b1) * g32
        v2 = b2 * v_.astype(jnp.float32) + (1 - b2) * g32 * g32
        p2 = p32 - lr * (m2 / c1) / (jnp.sqrt(v2 / c2) + eps)
        for out, x in zip(outs, (p2, m2, v2)):
            out.append(x.astype(p_.dtype))
    return tuple(jax.tree_util.tree_unflatten(treedef, o) for o in outs)


def client_step(fwd: Dict[str, Callable], sample: Callable, fed: Dict,
                batch: int, dp: bool, st: Dict, data_k, n_valid, key):
    """One local step of one client. ``st`` holds the ``private`` and
    ``proxy`` weights, their moments ``m_<role>``/``v_<role>`` and the step
    count ``t``. Returns the new state, both losses and the next key."""
    key, kb, kn = jax.random.split(key, 3)
    x, y = sample(data_k, jax.random.randint(kb, (batch,), 0, n_valid))
    phi, theta = st["private"], st["proxy"]
    peer_proxy = fwd["proxy"](theta, x)
    peer_priv = fwd["private"](phi, x)
    lp, g_phi = jax.value_and_grad(
        lambda p: dml(fwd["private"](p, x), peer_proxy, y, fed["alpha"]))(phi)

    def ex_loss(th, xi, yi, pi):
        return dml(fwd["proxy"](th, xi[None]), pi[None], yi[None],
                   fed["beta"])

    if dp:
        losses, grads = jax.vmap(jax.value_and_grad(ex_loss),
                                 in_axes=(None, 0, 0, 0))(theta, x, y,
                                                          peer_priv)
        norms = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)),
                                     axis=tuple(range(1, g.ndim)))
                             for g in jax.tree_util.tree_leaves(grads)))
        scale = 1.0 / jnp.maximum(1.0, norms / fed["dp_clip"])
        acc = [jnp.einsum("b...,b->...", g.astype(jnp.float32), scale)
               for g in jax.tree_util.tree_leaves(grads)]
        nkeys = jax.random.split(kn, len(acc))
        sd = fed["dp_sigma"] * fed["dp_clip"]
        g_theta = jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(theta),
            [((a + sd * jax.random.normal(k_, a.shape, jnp.float32)) / batch)
             for a, k_ in zip(acc, nkeys)])
        lq = jnp.mean(losses)
    else:
        lq, g_theta = jax.value_and_grad(
            lambda th: dml(fwd["proxy"](th, x), peer_priv, y, fed["beta"]))(
                theta)
    t = st["t"] + 1
    new = dict(st, t=t)
    new["private"], new["m_private"], new["v_private"] = adam(
        phi, g_phi, st["m_private"], st["v_private"], t, fed)
    new["proxy"], new["m_proxy"], new["v_proxy"] = adam(
        theta, g_theta, st["m_proxy"], st["v_proxy"], t, fed)
    return new, lp, lq, key


def exponential_mix(t: int, K: int) -> np.ndarray:
    """Column-stochastic P of round t: every client keeps half and sends
    half to the peer ``2^(t mod (floor(log2(K-1))+1))`` places ahead."""
    if K <= 1:
        return np.eye(K)
    offs = [2 ** i for i in range(int(np.floor(np.log2(K - 1))) + 1)]
    shift = offs[t % len(offs)]
    P = np.zeros((K, K))
    for k in range(K):
        P[k, k] += 0.5
        P[(k + shift) % K, k] += 0.5
    return P


def exchange(states: List[Dict], w, P: np.ndarray):
    """PushSum with de-bias over every client's proxy."""
    Pj = jnp.asarray(P, jnp.float32)
    w2 = Pj @ w
    mixed = jax.tree_util.tree_map(
        lambda *xs: (jnp.einsum("jk,k...->j...", Pj,
                                jnp.stack(xs).astype(jnp.float32))
                     / w2.reshape((-1,) + (1,) * xs[0].ndim)
                     ).astype(xs[0].dtype),
        *[s["proxy"] for s in states])
    out = []
    for k, s in enumerate(states):
        s = dict(s)
        s["proxy"] = jax.tree_util.tree_map(lambda x: x[k], mixed)
        out.append(s)
    return out, w2


def norms_per_leaf(tree) -> np.ndarray:
    return np.asarray([float(jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32))))) for x in jax.tree_util.tree_leaves(tree)])


def follow(family, models: Dict, fed: Dict, traffic: Dict, data: List,
           wkey, run_key, blocks: int, steps: int,
           dtype=jnp.float32) -> Dict:
    """Run the reference federation for ``blocks`` round-blocks of
    ``traffic["rounds_per_block"]`` rounds and ``steps`` local steps each.
    ``family`` is a reference family module such as ``reference_lm``.
    Returns what the check compares: ``loss`` [rounds, K, 2] (each round's
    last local step, private then proxy), ``moment`` {role: [K, leaves]}
    (the norm of Adam's first moment after the first block) and ``change``
    {role: [K, leaves]} (the norm of each weight's change after the last
    block)."""
    K, R = fed["clients"], traffic["rounds_per_block"]
    roles = ("private", "proxy")
    lays = {r: family.layout(models[r]) for r in roles}
    init = {r: [family_params(family, wkey, k, r, lays[r], models[r], dtype)
                for k in range(K)] for r in roles}
    states = []
    for k in range(K):
        st = {"t": jnp.zeros((), jnp.int32)}
        for r in roles:
            st[r] = init[r][k]
            st["m_" + r] = jax.tree_util.tree_map(jnp.zeros_like, st[r])
            st["v_" + r] = jax.tree_util.tree_map(jnp.zeros_like, st[r])
        states.append(st)
    w = jnp.ones((K,), jnp.float32)
    fwd = {r: (lambda p, x, m=models[r]: family.forward(p, m, x))
           for r in roles}
    batch = family.batch_size(fed, traffic)
    step = jax.jit(lambda st, d, nv, key: client_step(
        fwd, family.sample, fed, batch, traffic["dp"], st, d, nv, key))
    losses, moment = [], None
    with jax.default_matmul_precision("highest"):
        for b in range(blocks):
            for r in range(R):
                t = b * R + r
                rk = jax.random.fold_in(run_key, ROUND_KEY_OFFSET + t)
                row = []
                for k in range(K):
                    ck = jax.random.fold_in(rk, k)
                    nv = jnp.int32(family.n_examples(data[k]))
                    for _ in range(steps):
                        states[k], lp, lq, ck = step(states[k], data[k], nv,
                                                     ck)
                    row.append((float(lp), float(lq)))
                losses.append(row)
                states, w = exchange(states, w, exponential_mix(t, K))
            if b == 0:
                moment = {r: np.stack([norms_per_leaf(s["m_" + r])
                                       for s in states]) for r in roles}
        change = {r: np.stack([norms_per_leaf(jax.tree_util.tree_map(
            lambda a, b_: a.astype(jnp.float32) - b_.astype(jnp.float32),
            states[k][r], init[r][k])) for k in range(K)]) for r in roles}
    return {"loss": np.asarray(losses), "moment": moment, "change": change}


def family_params(family, wkey, k: int, role: str, lay, m: Dict, dtype):
    """Client ``k``'s ``role`` weights, made as the harness makes them."""
    p = weights.make_params(wkey, k, role, lay, family.rule(m))
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), p)
