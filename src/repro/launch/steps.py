"""LLM-scale ProxyFL steps: the paper's Algorithm 1 applied to the assigned
architectures.

* ``make_train_step`` builds ONE client's local DML step (Algorithm 1 lines
  2-5): the private model is updated on Eq. (4) without DP, the proxy on
  Eq. (5) with per-example DP-SGD (Eq. 7). ``core.engine.FederationEngine``
  stacks it over the clients and owns the round, the exchange and the
  round-block; this module builds no round of its own.
* ``init_train_state`` / ``train_state_shapes``, ``init_serve_state`` /
  ``serve_state_shapes`` and ``input_specs`` build the per-client state and
  its shapes (``jax.ShapeDtypeStruct`` stand-ins, never allocated).
* ``make_prefill_step`` / ``make_decode_step`` run inference on the
  client's private model (the paper: "After training, a client's private
  model can be used for inference").
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp

from ..configs.base import InputShape, ModelConfig, ProxyFLConfig
from ..core.dp import dp_gradient_chunked, non_dp_gradient
from ..nn.losses import dml_loss
from ..nn.model import forward, init_cache, init_model
from ..optim import Adam


@dataclass(frozen=True)
class StepOptions:
    """Implementation knobs (the §Perf hillclimb levers)."""

    remat: bool = True            # activation-checkpoint the layer-stack scan
    accum: int = 8                # private-grad microbatch accumulation chunks
    dp_chunk: int = 8             # examples per DP vmap chunk (scan over chunks)
    moment_dtype: str = "float32"  # Adam m/v dtype ("bfloat16" halves opt HBM)
    kv_chunk: int = 1024          # online-softmax KV chunk length
    mamba_chunk: int = 256        # Mamba chunked-scan block length


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — never allocated)


def input_specs(cfg: ModelConfig, shape: InputShape
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one client's step at ``shape``."""
    B, S = shape.global_batch, shape.seq_len

    def tok(shape_):
        return jax.ShapeDtypeStruct(shape_, jnp.int32)

    if shape.kind == "train":
        if cfg.modality == "audio":
            specs = {"tokens": tok((B, S, cfg.n_codebooks)),
                     "labels": tok((B, S, cfg.n_codebooks))}
        else:
            specs = {"tokens": tok((B, S)), "labels": tok((B, S))}
        if cfg.modality == "vlm":
            specs["img"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": tok((B, S, cfg.n_codebooks)) if cfg.modality == "audio"
                 else tok((B, S))}
        if cfg.modality == "vlm":
            specs["img"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        return specs
    if shape.kind == "decode":
        return {"tokens": tok((B, 1, cfg.n_codebooks)) if cfg.modality == "audio"
                else tok((B, 1)),
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# state construction (init fns; shapes via jax.eval_shape)


def init_train_state(key, cfg_priv: ModelConfig, cfg_proxy: ModelConfig,
                     fl: ProxyFLConfig, opts: StepOptions) -> Dict:
    opt = Adam(lr=fl.lr, weight_decay=fl.weight_decay, moment_dtype=opts.moment_dtype)
    kp, kx = jax.random.split(key)
    phi = init_model(kp, cfg_priv)
    theta = init_model(kx, cfg_proxy)
    return {
        "private": {"params": phi, "opt": opt.init(phi)},
        "proxy": {"params": theta, "opt": opt.init(theta)},
        "w": jnp.ones((), jnp.float32),   # PushSum de-bias weight
        "t": jnp.zeros((), jnp.int32),
    }


def train_state_shapes(cfg_priv, cfg_proxy, fl, opts) -> Dict:
    return jax.eval_shape(
        lambda k: init_train_state(k, cfg_priv, cfg_proxy, fl, opts),
        jax.random.PRNGKey(0))


def init_serve_state(key, cfg: ModelConfig, shape: InputShape) -> Dict:
    max_len = shape.seq_len + (cfg.n_image_tokens if cfg.modality == "vlm" else 0)
    return {"params": init_model(key, cfg),
            "cache": init_cache(cfg, shape.global_batch, max_len,
                                dtype=jnp.dtype(cfg.dtype))}


def serve_state_shapes(cfg, shape) -> Dict:
    return jax.eval_shape(
        lambda k: init_serve_state(k, cfg, shape), jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# losses


def _text_logits(cfg: ModelConfig, logits: jnp.ndarray) -> jnp.ndarray:
    """Drop image-position logits so labels align with text tokens."""
    if cfg.modality == "vlm" and cfg.n_image_tokens:
        return logits[:, cfg.n_image_tokens:]
    return logits


def _forward_logits(params, cfg: ModelConfig, tokens, img, opts: StepOptions):
    logits, _, aux = forward(params, cfg, tokens, img, remat=opts.remat,
                             kv_chunk=opts.kv_chunk, mamba_chunk=opts.mamba_chunk)
    return _text_logits(cfg, logits), aux


# ---------------------------------------------------------------------------
# train step (single client — Algorithm 1 lines 2–5)


def make_train_step(cfg_priv: ModelConfig, cfg_proxy: ModelConfig,
                    fl: ProxyFLConfig, opts: StepOptions = StepOptions()):
    opt = Adam(lr=fl.lr, weight_decay=fl.weight_decay, moment_dtype=opts.moment_dtype)

    def step(state, batch, key):
        phi0 = state["private"]["params"]
        theta0 = state["proxy"]["params"]

        # ---- private model: Eq. (4), non-DP, microbatch-accumulated.
        # The proxy peer logits are recomputed per microbatch inside the
        # loss (theta0 is closed over; accumulation slices tokens/labels/img
        # together through the batch dict). The loss also returns its own
        # logits at phi0: the proxy step's private peer.
        def ploss(phi, mb):
            t_, l_, i_ = mb["tokens"], mb["labels"], mb.get("img")
            peer, _ = _forward_logits(theta0, cfg_proxy, t_, i_, opts)
            own, aux = _forward_logits(phi, cfg_priv, t_, i_, opts)
            return dml_loss(own, peer, l_, fl.alpha) + aux, own

        # ---- proxy model: Eq. (5) with per-example DP-SGD (Eq. 7).
        # Its private peer logits depend only on phi0. Unsliced (accum 1),
        # the private step has just made them for the whole batch, so they
        # ride along as one more batch leaf. Microbatched, holding [B, S, V]
        # logits across the step would undo what accum bounds, so add_peer
        # recomputes them once per DP chunk (or microbatch) with a batched
        # forward, never per example.
        def add_peer(cb):
            peer, _ = _forward_logits(phi0, cfg_priv, cb["tokens"],
                                      cb.get("img"), opts)
            return dict(cb, peer=peer)

        with jax.named_scope("fl.private"):
            if opts.accum <= 1:
                g_phi, m_phi, own = non_dp_gradient(ploss, phi0, batch,
                                                    has_aux=True)
                peer = jax.lax.stop_gradient(own)
                pbatch, prepare = dict(batch, peer=peer), (lambda b: b)
            else:
                g_phi, m_phi = non_dp_gradient(lambda p, b: ploss(p, b)[0],
                                               phi0, batch, accum=opts.accum)
                pbatch, prepare = batch, add_peer

        def xloss(theta, ex):
            t_, l_, i_ = ex["tokens"], ex["labels"], ex.get("img")
            own, aux = _forward_logits(theta, cfg_proxy, t_, i_, opts)
            return dml_loss(own, ex["peer"], l_, fl.beta) + aux

        with jax.named_scope("fl.proxy"):
            if fl.dp.enabled:
                g_theta, m_theta = dp_gradient_chunked(
                    xloss, theta0, pbatch, key,
                    clip_norm=fl.dp.clip_norm,
                    noise_multiplier=fl.dp.noise_multiplier,
                    chunk=opts.dp_chunk,
                    prepare_chunk=prepare)
            else:
                g_theta, m_theta = non_dp_gradient(
                    lambda th, b: xloss(th, prepare(b)), theta0, pbatch,
                    accum=opts.accum)

        phi1, opt_phi1 = opt.update(g_phi, state["private"]["opt"], phi0)
        theta1, opt_theta1 = opt.update(g_theta, state["proxy"]["opt"], theta0)
        new_state = {
            "private": {"params": phi1, "opt": opt_phi1},
            "proxy": {"params": theta1, "opt": opt_theta1},
            "w": state["w"],
            "t": state["t"] + 1,
        }
        metrics = {"private_loss": m_phi["loss"], "proxy_loss": m_theta["loss"]}
        return new_state, metrics

    return step


# ---------------------------------------------------------------------------
# serve steps (private model inference)


def make_prefill_step(cfg: ModelConfig, opts: StepOptions = StepOptions()):
    def prefill(state, batch):
        tokens = batch["tokens"]
        img = batch.get("img")
        logits, cache, _ = forward(state["params"], cfg, tokens, img,
                                   cache=state["cache"], pos_offset=0,
                                   kv_chunk=opts.kv_chunk,
                                   mamba_chunk=opts.mamba_chunk)
        return {"params": state["params"], "cache": cache}, logits[:, -1]

    return prefill


def make_decode_step(cfg: ModelConfig, opts: StepOptions = StepOptions()):
    def decode(state, batch):
        tokens = batch["tokens"]          # [B, 1] (or [B, 1, K] audio)
        pos = batch["pos"]                # scalar int32 — current length
        logits, cache, _ = forward(state["params"], cfg, tokens,
                                   cache=state["cache"], pos_offset=pos,
                                   kv_chunk=opts.kv_chunk,
                                   mamba_chunk=opts.mamba_chunk)
        return {"params": state["params"], "cache": cache}, logits[:, -1]

    return decode
