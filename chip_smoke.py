"""Bring-up smoke test: ProxyFL training on a TPU through its normal entry
points, with random weights made from a seed.

One process, no children, and no fallback: it exits non-zero and prints no
result when JAX finds no TPU, or when it runs away from the rest of this
repository. Phases (default, one chip):

(a) the LLM training path, ``repro.launch.train`` at the full width of
    ``--preset 100m`` (private d768 x 12 layers, vocab 8192; proxy d256 x 4
    layers): 4 clients, DP-SGD on the proxy (sigma=1, C=1), the ``vmap``
    backend, 2 rounds per round-block. A ``--use-pallas`` run saves one
    checkpoint, a second run resumes from it with ``--verify-commitments``,
    and a third run takes the plain-XLA exchange.
(b) the paper's classifier protocol, ``run_federated("proxyfl", ...)`` in
    the CIFAR-10 setting (8 clients, B=250, CNN2 private / CNN1 proxy models
    of ``repro.nn.vision``) with ``use_pallas=True``, which runs the fused
    DP kernels and the fused exchange.

Checks: the platform is TPU and the kernels compile for Mosaic (no
interpret mode); the compiled exchange holds a ``tpu_custom_call``; losses,
accuracies and parameters are finite; the PushSum weights sum to K; the
fused exchange and the fused DP step agree with their plain-XLA paths at the
same key, within the tolerances of tests/test_conformance.py, and the
exchange with a float64 reference.

``--four-chips`` runs only the cross-silo phase: phase (a)'s federation with
``--backend shard_map`` (one client per chip, ppermute exchange) against the
``vmap`` backend on one chip of the same host, at the same seed.

Earlier lines print the device kind, compile seconds, seconds per round
after warm-up and peak device bytes; these are bring-up readings, not
benchmark results. The last line is the JSON verdict.

Usage::

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # a four-chip host
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"
WORK = ROOT / ".chip_smoke"   # checkpoints of phase (a); git-ignored

#: the conformance "close" grade (tests/test_conformance.py)
CLOSE = dict(atol=1e-5, rtol=1e-4)

LLM_ARGS = ["--preset", "100m", "--clients", "4", "--steps-per-round", "4",
            "--batch", "8", "--seq", "128", "--sigma", "1", "--clip", "1",
            "--lr", "1e-3", "--rounds-per-block", "2", "--seed", "0"]

#: share of coordinates two differently compiled runs may leave outside
#: CLOSE (see phase_four_chips)
MAX_OUTSIDE_CLOSE = 1e-5


class Compiles:
    """Seconds JAX spent in backend compilation (persistent-cache reads
    included, which is what makes a warm cache show up as fewer seconds)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def _peak_bytes(device) -> int:
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def _flat_proxies(state):
    import jax
    import numpy as np

    from repro.nn.modules import tree_flatten_vector

    return np.asarray(jax.vmap(tree_flatten_vector)(
        state["proxy"]["params"]))


def _steady_round_seconds(res, rounds_per_block: int) -> float:
    """Device seconds per round over the blocks after the first (the first
    block includes compilation)."""
    later = res["block_seconds"][1:]
    return sum(later) / (len(later) * rounds_per_block) if later else \
        float("nan")


def check_exchange(state, K: int, t: int) -> None:
    """The fused exchange on the trained proxies: compiled for Mosaic,
    close to the plain-XLA path, close to a float64 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.gossip import mix_matrix, pushsum_mix_debiased
    from repro.nn.modules import tree_flatten_vector

    flat = jax.vmap(tree_flatten_vector)(state["proxy"]["params"])
    w = jnp.asarray(state["w"], flat.dtype)
    P = jnp.asarray(mix_matrix("pushsum", t, K, "exponential"), jnp.float32)
    fused = jax.jit(lambda f, w_, p: pushsum_mix_debiased(
        f, w_, p, use_pallas=True))
    _check("tpu_custom_call" in fused.lower(flat, w, P).compile().as_text(),
           "the compiled fused exchange holds no tpu_custom_call")
    z_f, w_f = fused(flat, w, P)
    z_p, w_p = pushsum_mix_debiased(flat, w, P, use_pallas=False)
    np.testing.assert_allclose(np.asarray(z_f), np.asarray(z_p), **CLOSE)
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_p), **CLOSE)
    P64, f64 = np.asarray(P, np.float64), np.asarray(flat, np.float64)
    w64 = P64 @ np.asarray(w, np.float64)
    np.testing.assert_allclose(np.asarray(z_p), (P64 @ f64) / w64[:, None],
                               **CLOSE)
    _check(abs(float(np.sum(w64)) - K) < 1e-4, f"PushSum weights sum to "
           f"{float(np.sum(w64))}, not {K}")


def phase_llm(llm_args, work: Path, compiles: Compiles) -> None:
    """(a) ``repro.launch.train`` with and without the fused exchange, one
    checkpoint, and a verified resume from it."""
    import jax
    import numpy as np

    from repro.launch import train

    dev = jax.devices()[0]
    K = int(llm_args[llm_args.index("--clients") + 1])
    rpb = int(llm_args[llm_args.index("--rounds-per-block") + 1])
    ckpt = ["--checkpoint-dir", str(work / "llm"), "--checkpoint-every", "4"]
    runs = [("pallas", ["--use-pallas", "--rounds", "4"] + ckpt),
            ("resume", ["--use-pallas", "--rounds", "6", "--resume",
                        "--verify-commitments"] + ckpt),
            ("plain", ["--rounds", "4"])]
    for name, extra in runs:
        c0 = compiles.seconds
        res = train.run(llm_args + extra)
        m = res["metrics"]
        for key in ("private_loss", "proxy_loss"):
            _check(bool(np.isfinite(m[key]).all()), f"{name}: {key} {m[key]}")
        w = np.asarray(res["state"]["w"])
        _check(abs(float(w.sum()) - K) < 1e-4, f"{name}: PushSum weights "
               f"sum to {float(w.sum())}, not {K}")
        _check(bool(np.isfinite(_flat_proxies(res["state"])).all()),
               f"{name}: non-finite proxy parameters")
        if name == "resume":
            _check(len(res["block_seconds"]) == 1,
                   "resume did not continue from the round-4 checkpoint")
            check_exchange(res["state"], K, t=6)
        print(f"[smoke] llm/{name}: blocks={len(res['block_seconds'])} "
              f"compile_s={compiles.seconds - c0:.3f} "
              f"first_block_s={res['block_seconds'][0]:.3f} "
              f"steady_s_per_round={_steady_round_seconds(res, rpb):.4f} "
              f"peak_bytes={_peak_bytes(dev)}", flush=True)
        del res


def check_dp_step(proxy, x, y, cfg) -> None:
    """The fused DP-SGD + Adam step of the proxy against the plain
    ``dp_gradient`` + ``Adam.update`` chain, same batch and key."""
    import jax
    import numpy as np

    from repro.core.dp import dp_adam_update, dp_gradient
    from repro.nn.losses import cross_entropy
    from repro.optim import Adam

    k = jax.random.PRNGKey(11)
    params = proxy.init(k)
    opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)
    state = opt.init(params)
    batch = (x[:cfg.batch_size], y[:cfg.batch_size])

    def loss(p, b):
        return cross_entropy(proxy.apply(p, b[0]), b[1])

    dp = dict(clip_norm=cfg.dp.clip_norm,
              noise_multiplier=cfg.dp.noise_multiplier)
    fused = jax.jit(lambda p, s, b, key: dp_adam_update(
        loss, p, s, b, key, opt=opt, **dp))
    hlo = fused.lower(params, state, batch, k).compile().as_text()
    _check("tpu_custom_call" in hlo, "the fused DP step holds no "
           "tpu_custom_call")
    p2, _, _ = fused(params, state, batch, k)
    g, _ = jax.jit(lambda p, b, key: dp_gradient(loss, p, b, key, **dp))(
        params, batch, k)
    p2_ref, _ = opt.update(g, state, params)
    for a, b in zip(jax.tree_util.tree_leaves(p2),
                    jax.tree_util.tree_leaves(p2_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **CLOSE)


def phase_paper(n_clients: int, rounds: int, n_train_factor: float,
                compiles: Compiles) -> None:
    """(b) ``run_federated("proxyfl", ...)`` in the paper's CIFAR-10
    setting with the fused DP kernels and exchange."""
    import jax
    import numpy as np

    from benchmarks.common import federation_data, spec_of
    from repro.configs.paper_small import paper_benchmark_protocol
    from repro.core.baselines import run_federated

    dev = jax.devices()[0]
    data, test, d = federation_data("cifar10", n_clients, 0,
                                    n_train_factor=n_train_factor)
    private = spec_of("cnn2", d["shape"], d["n_classes"])
    proxy = spec_of("cnn1", d["shape"], d["n_classes"])
    cfg = paper_benchmark_protocol(n_clients=n_clients, rounds=rounds,
                                   use_pallas=True)
    check_dp_step(proxy, *data[0], cfg)
    c0 = compiles.seconds
    res = run_federated("proxyfl", [private] * n_clients, proxy, data, test,
                        cfg, backend="vmap", rounds_per_block=2,
                        eval_every=rounds)
    row = res["history"][-1]
    for key in ("private_acc", "proxy_acc"):
        acc = np.asarray(row[key])
        _check(bool(np.isfinite(acc).all() and (acc >= 0).all()
                    and (acc <= 1).all()), f"paper: {key} {acc}")
    w = sum(c.w for c in res["clients"])
    _check(abs(w - n_clients) < 1e-4, f"paper: PushSum weights sum to {w}")
    eps = res["epsilon"]
    _check(all(e is not None and np.isfinite(e) for e in eps),
           f"paper: epsilon {eps}")
    for c in res["clients"]:
        for leaf in jax.tree_util.tree_leaves((c.private_params,
                                               c.proxy_params)):
            _check(bool(np.isfinite(np.asarray(leaf)).all()),
                   "paper: non-finite parameters")
    print(f"[smoke] paper/cifar10: clients={n_clients} rounds={rounds} "
          f"private_acc={np.mean(row['private_acc']):.4f} "
          f"proxy_acc={np.mean(row['proxy_acc']):.4f} eps={max(eps):.3f} "
          f"compile_s={compiles.seconds - c0:.3f} "
          f"peak_bytes={_peak_bytes(dev)}", flush=True)


def phase_four_chips(llm_args, compiles: Compiles) -> None:
    """Cross-silo: one client per chip (shard_map) against vmap on one
    chip, same seed; the final proxies must agree.

    The two backends are different compiled programs, so their matmuls
    round differently, and Adam turns a sign flip of a near-zero gradient
    into a step of about ``lr`` either way. So a handful of coordinates may
    differ by up to ``2·lr`` per local step; all others must agree at the
    conformance "close" grade. A wrong exchange (wrong peer, weight or
    de-bias) moves most coordinates, by the spread between clients."""
    import jax
    import numpy as np

    from repro.launch import train

    K = int(llm_args[llm_args.index("--clients") + 1])
    devices = jax.devices()
    _check(len(devices) >= K, f"--four-chips needs {K} devices, found "
           f"{len(devices)}")
    rounds = 2
    args = llm_args + ["--rounds", str(rounds), "--use-pallas"]
    c0 = compiles.seconds
    res = train.run(args + ["--backend", "shard_map"])
    state = res["state"]
    client_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(state)) / K
    for x in jax.tree_util.tree_leaves(state):
        _check(x.sharding.shard_shape(x.shape)[0] == 1,
               f"a stacked leaf {x.shape} is not one client per chip")
    held = [int((d.memory_stats() or {}).get("bytes_in_use", -1))
            for d in devices[:K]]
    _check(all(0.9 * client_bytes <= b < 1.9 * client_bytes for b in held),
           f"bytes in use per chip {held}, one client is {client_bytes:.0f}")
    print(f"[smoke] four_chips/shard_map: bytes_in_use={held} "
          f"client_state_bytes={client_bytes:.0f} "
          f"compile_s={compiles.seconds - c0:.3f} "
          f"first_block_s={res['block_seconds'][0]:.3f}", flush=True)
    sharded = _flat_proxies(state)
    del res, state
    c0 = compiles.seconds
    res = train.run(args + ["--backend", "vmap"])
    single = _flat_proxies(res["state"])
    diff = float(np.max(np.abs(sharded - single)))
    outside = float(np.mean(~np.isclose(sharded, single, **CLOSE)))
    steps = rounds * int(llm_args[llm_args.index("--steps-per-round") + 1])
    bound = 2 * float(llm_args[llm_args.index("--lr") + 1]) * steps
    print(f"[smoke] four_chips/vmap_one_chip: max_abs_diff={diff:.3e} "
          f"share_outside_close={outside:.3e} "
          f"compile_s={compiles.seconds - c0:.3f} "
          f"first_block_s={res['block_seconds'][0]:.3f}", flush=True)
    _check(outside <= MAX_OUTSIDE_CLOSE and diff <= bound,
           f"shard_map vs vmap proxies: {outside:.3e} of coordinates "
           f"outside {CLOSE}, max |diff| {diff:.3e} (bound {bound:.3e})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the shard_map phase on a four-chip host")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(SRC), str(ROOT)]
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    from repro.kernels import default_interpret
    from repro.launch.compile_cache import use_compile_cache

    _check(not default_interpret(), "Pallas kernels would run interpreted")
    print(f"[smoke] device_kind={dev.device_kind} "
          f"devices={jax.device_count()} "
          f"compile_cache={use_compile_cache()}", flush=True)
    compiles = Compiles()
    shutil.rmtree(WORK, ignore_errors=True)
    try:
        if args.four_chips:
            phase_four_chips(LLM_ARGS, compiles)
        else:
            phase_llm(LLM_ARGS, WORK, compiles)
            phase_paper(8, 4, 1.0, compiles)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    print(f"[smoke] compile_s_total={compiles.seconds:.3f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
