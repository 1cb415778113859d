"""ProxyFL — Algorithm 1 of the paper, plus the generic client machinery
shared with the baselines.

A *ModelSpec* abstracts any classifier (vision CNN, LLM, ...) as
``init(key) -> params`` / ``apply(params, x) -> logits``; ProxyFL only ever
touches models through this interface, which is what gives the protocol its
model-heterogeneity (paper challenge (i)).

Each client holds a private model (trained WITHOUT DP, Eq. 4) and a proxy
model (trained WITH DP-SGD, Eq. 5/7). Per round: ``local_steps`` joint DML
steps, then one PushSum gossip exchange of the proxies (§3.4).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ProxyFLConfig
from ..nn.losses import cross_entropy, dml_loss
from ..nn.modules import tree_flatten_vector, tree_unflatten_vector
from ..optim import Adam
from .accountant import PrivacyAccountant
from .dp import dp_adam_update, dp_gradient, non_dp_gradient
from .gossip import debias, pushsum_mix

Params = Any


@dataclass(frozen=True)
class ModelSpec:
    name: str
    init: Callable[[Any], Params]
    apply: Callable[[Params, jnp.ndarray], jnp.ndarray]


@dataclass
class ClientState:
    private_params: Params
    private_opt: Any
    proxy_params: Params
    proxy_opt: Any
    w: float = 1.0  # PushSum de-bias weight (Algorithm 1)
    accountant: Optional[PrivacyAccountant] = None


# ---------------------------------------------------------------------------
# step builders (cached per (spec, cfg) so federations reuse XLA code).
# ``*_step_fn`` returns the raw traceable function — the FederationEngine
# composes it under its own jit/vmap/scan; ``make_*_step`` wraps it in
# jax.jit for direct per-step callers.


@functools.lru_cache(maxsize=None)
def dml_step_fn(private_spec: ModelSpec, proxy_spec: ModelSpec,
                cfg: ProxyFLConfig):
    """One joint DML step (Algorithm 1 lines 3-5): private non-DP update of
    Eq. (4), proxy DP-SGD update of Eq. (5)/(7), both at round-start params."""
    opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)

    def private_loss(phi, batch, theta):
        x, y = batch
        peer = proxy_spec.apply(theta, x)
        return dml_loss(private_spec.apply(phi, x), peer, y, cfg.alpha)

    def proxy_loss(theta, batch, phi):
        x, y = batch
        peer = private_spec.apply(phi, x)
        return dml_loss(proxy_spec.apply(theta, x), peer, y, cfg.beta)

    def step(phi, opt_phi, theta, opt_theta, batch, key):
        # proxy first in code order, but both use round-start params
        if cfg.dp.enabled and cfg.use_pallas:
            # fused clip→noise→Adam hot path (repro.kernels); allclose to
            # the dp_gradient + opt.update chain below, never bit-exact
            with jax.named_scope("fl.proxy"):
                theta2, opt_theta2, m_theta = dp_adam_update(
                    lambda t, b: proxy_loss(t, b, phi), theta, opt_theta,
                    batch, key, opt=opt, clip_norm=cfg.dp.clip_norm,
                    noise_multiplier=cfg.dp.noise_multiplier)
        else:
            with jax.named_scope("fl.proxy"):
                if cfg.dp.enabled:
                    g_theta, m_theta = dp_gradient(
                        lambda t, b: proxy_loss(t, b, phi), theta, batch,
                        key, clip_norm=cfg.dp.clip_norm,
                        noise_multiplier=cfg.dp.noise_multiplier,
                        vectorized=cfg.dp.vectorized)
                else:
                    g_theta, m_theta = non_dp_gradient(
                        lambda t, b: proxy_loss(t, b, phi), theta, batch)
            theta2, opt_theta2 = opt.update(g_theta, opt_theta, theta)
        with jax.named_scope("fl.private"):
            g_phi, m_phi = non_dp_gradient(
                lambda p, b: private_loss(p, b, theta), phi, batch)
        phi2, opt_phi2 = opt.update(g_phi, opt_phi, phi)
        return phi2, opt_phi2, theta2, opt_theta2, {
            "private_loss": m_phi["loss"], "proxy_loss": m_theta["loss"]}

    return step


@functools.lru_cache(maxsize=None)
def make_dml_step(private_spec: ModelSpec, proxy_spec: ModelSpec,
                  cfg: ProxyFLConfig):
    return jax.jit(dml_step_fn(private_spec, proxy_spec, cfg))


@functools.lru_cache(maxsize=None)
def ce_step_fn(spec: ModelSpec, cfg: ProxyFLConfig, dp: bool):
    """Plain CE step for single-model methods (FedAvg/AvgPush/CWT/...)."""
    opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)

    def loss(params, batch):
        x, y = batch
        return cross_entropy(spec.apply(params, x), y)

    def step(params, opt_state, batch, key):
        if dp and cfg.use_pallas:
            params2, opt_state2, m = dp_adam_update(
                loss, params, opt_state, batch, key, opt=opt,
                clip_norm=cfg.dp.clip_norm,
                noise_multiplier=cfg.dp.noise_multiplier)
        elif dp:
            g, m = dp_gradient(loss, params, batch, key,
                               clip_norm=cfg.dp.clip_norm,
                               noise_multiplier=cfg.dp.noise_multiplier,
                               vectorized=cfg.dp.vectorized)
            params2, opt_state2 = opt.update(g, opt_state, params)
        else:
            g, m = non_dp_gradient(loss, params, batch)
            params2, opt_state2 = opt.update(g, opt_state, params)
        return params2, opt_state2, m["loss"]

    return step


@functools.lru_cache(maxsize=None)
def make_ce_step(spec: ModelSpec, cfg: ProxyFLConfig, dp: bool):
    return jax.jit(ce_step_fn(spec, cfg, dp))


# ---------------------------------------------------------------------------
# gossip over heterogeneous client states (thin wrapper over the engine's
# mixing rule — see repro.core.engine for the on-device backends)


def gossip_proxies(clients: List[ClientState], t: int, cfg: ProxyFLConfig,
                   active=None) -> None:
    """Algorithm 1 lines 7-11 (in place). Proxies share one architecture, so
    they stack into Θ ∈ R^{K×d} and one matmul applies P^(t). ``active``
    drops clients out of the exchange (§3.4)."""
    from .gossip import mix_matrix

    K = len(clients)
    if K <= 1:
        return
    like = clients[0].proxy_params
    thetas = jnp.stack([tree_flatten_vector(c.proxy_params) for c in clients])
    ws = jnp.asarray([c.w for c in clients], thetas.dtype)
    P = mix_matrix("pushsum", t, K, cfg.topology, active)
    mixed_t, mixed_w = pushsum_mix(thetas, ws, P)
    unbiased = debias(mixed_t, mixed_w)
    for k, c in enumerate(clients):
        c.proxy_params = tree_unflatten_vector(unbiased[k], like)
        c.w = float(mixed_w[k])


# ---------------------------------------------------------------------------
# federation driver


def init_client(key, private_spec: ModelSpec, proxy_spec: ModelSpec,
                cfg: ProxyFLConfig, n_local: int) -> ClientState:
    kf, kh = jax.random.split(key)
    opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)
    phi = private_spec.init(kf)
    theta = proxy_spec.init(kh)
    acc = None
    if cfg.dp.enabled:
        q = cfg.dp.sample_rate or min(1.0, cfg.batch_size / max(n_local, 1))
        acc = PrivacyAccountant(cfg.dp.noise_multiplier, q, cfg.dp.delta)
    return ClientState(phi, opt.init(phi), theta, opt.init(theta), 1.0, acc)


def local_round(client: ClientState, spec_pair, data, key, cfg: ProxyFLConfig
                ) -> Dict[str, float]:
    """One client's local optimization for one round (Algorithm 1 lines 2-5)."""
    private_spec, proxy_spec = spec_pair
    x, y = data
    step = make_dml_step(private_spec, proxy_spec, cfg)
    n_steps = cfg.local_steps or max(1, x.shape[0] // cfg.batch_size)
    phi, opt_phi = client.private_params, client.private_opt
    theta, opt_theta = client.proxy_params, client.proxy_opt
    last = {}
    for s in range(n_steps):
        key, kb, kn = jax.random.split(key, 3)
        idx = jax.random.randint(kb, (cfg.batch_size,), 0, x.shape[0])
        batch = (x[idx], y[idx])
        phi, opt_phi, theta, opt_theta, last = step(
            phi, opt_phi, theta, opt_theta, batch, kn)
        if client.accountant is not None:
            client.accountant.step()
    client.private_params, client.private_opt = phi, opt_phi
    client.proxy_params, client.proxy_opt = theta, opt_theta
    return {k: float(v) for k, v in last.items()}


def proxyfl_round(clients, spec_pairs, datasets, t, key, cfg: ProxyFLConfig,
                  active=None):
    """One full ProxyFL round across all clients: local DML then gossip.

    Thin wrapper over :class:`repro.core.engine.FederationEngine` (loop
    backend — the one that supports heterogeneous private architectures);
    mutates the ClientState list in place like the historical driver."""
    from .engine import dml_engine

    engine = dml_engine(tuple(p for p, _ in spec_pairs), spec_pairs[0][1],
                        cfg, backend="loop")
    states = [
        {"private": {"params": c.private_params, "opt": c.private_opt},
         "proxy": {"params": c.proxy_params, "opt": c.proxy_opt},
         "w": jnp.asarray(c.w, jnp.float32)}
        for c in clients
    ]
    engine.attach_accountants([c.accountant for c in clients])
    states, metrics = engine.run_round(states, list(datasets), t, key,
                                       active=active)
    for c, s in zip(clients, states):
        c.private_params, c.private_opt = s["private"]["params"], s["private"]["opt"]
        c.proxy_params, c.proxy_opt = s["proxy"]["params"], s["proxy"]["opt"]
        c.w = float(s["w"])
    return [{m: float(v[k]) for m, v in metrics.items()}
            for k in range(len(clients))]


@functools.lru_cache(maxsize=None)
def _eval_apply(spec: ModelSpec):
    """Jitted ``spec.apply``, hoisted out of the evaluation batch loop (a
    fresh ``jax.jit`` per batch would re-hash params every call)."""
    return jax.jit(spec.apply)


def evaluate(spec: ModelSpec, params, x, y, batch: int = 512) -> float:
    apply = _eval_apply(spec)
    correct = 0
    for i in range(0, x.shape[0], batch):
        logits = apply(params, x[i : i + batch])
        correct += int(jnp.sum(jnp.argmax(logits, -1) == y[i : i + batch]))
    return correct / x.shape[0]


@functools.lru_cache(maxsize=None)
def _eval_apply_batched(spec: ModelSpec):
    """Jitted cohort-batched ``spec.apply``: params carry a leading client
    dim, the eval batch is shared — the whole cohort's correct-counts come
    back as ONE [K] array instead of K sequential device->host pulls."""
    def batched(stacked_params, x, y):
        logits = jax.vmap(spec.apply, in_axes=(0, None))(stacked_params, x)
        return jnp.sum(jnp.argmax(logits, -1) == y[None, :], axis=1)

    return jax.jit(batched)


def evaluate_batched(spec: ModelSpec, stacked_params, x, y,
                     batch: int = 512) -> List[float]:
    """Test accuracy of every client at once (stacked [K, ...] params,
    shared test set). Per eval batch the correct-counts accumulate ON
    DEVICE; the single [K] host pull happens once at the end — the
    round-block counterpart of :func:`evaluate` (which pulls a float per
    client per batch)."""
    apply = _eval_apply_batched(spec)
    correct = None
    for i in range(0, x.shape[0], batch):
        c = apply(stacked_params, x[i : i + batch], y[i : i + batch])
        correct = c if correct is None else correct + c
    counts = np.asarray(correct)
    return [float(c) / x.shape[0] for c in counts]
