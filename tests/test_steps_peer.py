"""Where the LLM local step's proxy gets its private peer logits.

With the whole batch in one slice (``accum`` 1) the private step's own
forward at phi0 already holds the peer the proxy step distils from, so
``make_train_step`` passes those logits on instead of running the private
model a second time. These tests hold that step to one that recomputes the
peer with a separate private forward, and check in the compiled program
that the proxy step runs no private-width matmul."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import DPConfig, ProxyFLConfig
from repro.configs.registry import proxy_of, smoke_variant
from repro.core.dp import dp_gradient_chunked, non_dp_gradient
from repro.launch.steps import (StepOptions, _forward_logits,
                                init_train_state, make_train_step)
from repro.nn.losses import dml_loss
from repro.optim import Adam

B, S = 4, 16
# private widths that no proxy or batch dimension shares, so a matmul's
# operand shapes say which model it belongs to
D_PRIV, FF_PRIV = 160, 352


def _models():
    base = smoke_variant(get_config("qwen1.5-4b")).with_(dtype="float32")
    proxy = smoke_variant(proxy_of(base))
    priv = base.with_(d_model=D_PRIV, head_dim=D_PRIV // base.n_heads,
                      d_ff=FF_PRIV)
    assert {D_PRIV, FF_PRIV}.isdisjoint(
        {proxy.d_model, proxy.d_ff, proxy.vocab_size, B, S})
    return priv, proxy


def _setup(dp: bool, accum: int = 1):
    priv, proxy = _models()
    fl = ProxyFLConfig(dp=DPConfig(enabled=dp), batch_size=B)
    opts = StepOptions(remat=False, accum=accum, dp_chunk=2)
    state = init_train_state(jax.random.PRNGKey(0), priv, proxy, fl, opts)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              priv.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    return priv, proxy, fl, opts, state, batch


def _recompute_step(cfg_priv, cfg_proxy, fl, opts):
    """The local step with the private peer recomputed by its own forward
    at phi0, from the public pieces."""
    opt = Adam(lr=fl.lr, weight_decay=fl.weight_decay,
               moment_dtype=opts.moment_dtype)

    def step(state, batch, key):
        phi0 = state["private"]["params"]
        theta0 = state["proxy"]["params"]

        def ploss(phi, mb):
            peer, _ = _forward_logits(theta0, cfg_proxy, mb["tokens"], None, opts)
            own, aux = _forward_logits(phi, cfg_priv, mb["tokens"], None, opts)
            return dml_loss(own, peer, mb["labels"], fl.alpha) + aux

        def add_peer(cb):
            peer, _ = _forward_logits(phi0, cfg_priv, cb["tokens"], None, opts)
            return dict(cb, peer=peer)

        def xloss(theta, ex):
            own, aux = _forward_logits(theta, cfg_proxy, ex["tokens"], None, opts)
            return dml_loss(own, ex["peer"], ex["labels"], fl.beta) + aux

        g_phi, _ = non_dp_gradient(ploss, phi0, batch)
        if fl.dp.enabled:
            g_theta, _ = dp_gradient_chunked(
                xloss, theta0, batch, key, clip_norm=fl.dp.clip_norm,
                noise_multiplier=fl.dp.noise_multiplier, chunk=opts.dp_chunk,
                prepare_chunk=add_peer)
        else:
            g_theta, _ = non_dp_gradient(
                lambda th, b: xloss(th, add_peer(b)), theta0, batch)
        phi1, o_phi = opt.update(g_phi, state["private"]["opt"], phi0)
        theta1, o_theta = opt.update(g_theta, state["proxy"]["opt"], theta0)
        return {"private": {"params": phi1, "opt": o_phi},
                "proxy": {"params": theta1, "opt": o_theta}}

    return step


def _assert_models_close(got, want, **tol):
    for role in ("private", "proxy"):
        g, w = got[role], want[role]
        for name, a, b in (("params", g["params"], w["params"]),
                           ("m", g["opt"].m, w["opt"].m),
                           ("v", g["opt"].v, w["opt"].v)):
            jax.tree_util.tree_map_with_path(
                lambda path, x, y: np.testing.assert_allclose(
                    np.asarray(x), np.asarray(y), **tol,
                    err_msg=f"{role} {name} {jax.tree_util.keystr(path)}"),
                a, b)


@pytest.mark.parametrize("dp", [True, False], ids=["dp", "nodp"])
def test_peer_from_private_forward_matches_recompute(dp):
    priv, proxy, fl, opts, state, batch = _setup(dp)
    key = jax.random.PRNGKey(7)
    want = jax.jit(_recompute_step(priv, proxy, fl, opts))(state, batch, key)
    got, metrics = jax.jit(make_train_step(priv, proxy, fl, opts))(
        state, batch, key)
    assert bool(jnp.isfinite(metrics["private_loss"]))
    assert bool(jnp.isfinite(metrics["proxy_loss"]))
    _assert_models_close(got, want, rtol=1e-6, atol=1e-9)


@pytest.mark.parametrize("dp", [True, False], ids=["dp", "nodp"])
def test_microbatched_step_recomputes_peer_and_matches(dp):
    """``accum`` 2 keeps the per-slice recompute; it matches the unsliced
    step up to the rounding of accumulating two half-batch gradients."""
    priv, proxy, fl, opts, state, batch = _setup(dp, accum=2)
    key = jax.random.PRNGKey(7)
    got, metrics = jax.jit(make_train_step(priv, proxy, fl, opts))(
        state, batch, key)
    assert bool(jnp.isfinite(metrics["proxy_loss"]))
    want, _ = jax.jit(make_train_step(priv, proxy, fl, opts.with_(accum=1)))(
        state, batch, key)
    for role in ("private", "proxy"):
        for x, y in zip(jax.tree_util.tree_leaves(got[role]["opt"].m),
                        jax.tree_util.tree_leaves(want[role]["opt"].m)):
            scale = float(jnp.max(jnp.abs(y)))
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-4, atol=1e-5 * scale)


@pytest.mark.parametrize("accum", [1, 2])
def test_proxy_step_runs_private_matmuls_only_when_microbatched(accum):
    """Unsliced, no matmul under ``fl.proxy`` touches a private width;
    microbatched, the proxy step still runs the private forward."""
    priv, proxy, fl, opts, state, batch = _setup(True, accum=accum)
    hlo = jax.jit(make_train_step(priv, proxy, fl, opts)).lower(
        state, batch, jax.random.PRNGKey(7)).compile().as_text()
    # the compiled text names a matmul's operands without their shapes:
    # read each instruction's shape where it is defined
    shapes = dict(re.findall(r"%(\S+) = \w+\[([\d,]*)\]", hlo))
    matmuls = [line for line in hlo.splitlines()
               if re.search(r"= \S+ (dot|convolution)\(", line)]
    proxy_mm = [m for m in matmuls if re.search(r'op_name="[^"]*fl\.proxy/', m)]
    assert proxy_mm
    widths = {str(D_PRIV), str(FF_PRIV)}

    def private_width(line):
        operands = re.findall(r"%([^,\s)]+)",
                              line.split("(", 1)[1].split(")", 1)[0])
        return any(widths & set(shapes[o].split(",")) for o in operands)

    assert any(map(private_width, proxy_mm)) == (accum > 1)
