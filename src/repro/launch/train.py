"""End-to-end ProxyFL training driver for the LLM-scale path.

Runs the full protocol — per-round local DML steps (private non-DP +
proxy DP-SGD, Algorithm 1 lines 2–5) followed by the PushSum proxy
exchange (lines 7–11) — across K simulated clients, each holding a
private model of the selected architecture family and the shared proxy
architecture, on synthetic non-IID language-modelling data.

Rounds are executed by :class:`repro.core.engine.FederationEngine`
driving ``make_train_step``: with the default ``--backend vmap`` the whole
round (scan over local steps × vmap over clients × on-device PushSum
matmul) is ONE compiled XLA program; ``--rounds-per-block B`` goes
further and fuses B consecutive rounds into one engine round-block (outer
scan over rounds, stacked ``mix_schedule`` exchange matrices, in-scan RNG
folding) so the host syncs only at block edges — bit-identical to
per-round execution, with checkpoints landing on block edges.
``--backend loop`` keeps the per-client dispatch (useful for debugging /
heterogeneous experiments). ``--backend async --staleness T`` switches to
the stale-gossip exchange: the round-t mix merges neighbor proxy mass put
in flight τ rounds earlier (communication overlapped with the local
scans, Assran et al. 2019; τ=0 is bit-identical to vmap). ``--backend hier
--n-shards S`` runs the two-level cohort: block-diagonal intra-shard
matmul mixing plus at-most-one sparse cross-shard edge per client per
round — the same flat ``mix_schedule`` matrices factored by edge
locality, bit-identical to vmap at τ=0; ``--staleness`` then delays only
the cross-shard edges. ``--dropout-rate``
exercises the §3.4 dropout/join scenario: clients sit rounds out and the
time-varying gossip graph re-knits around them.

On CPU this runs the reduced (smoke) variant of the chosen architecture;
the full-size configs are exercised through ``dryrun.py``. The default
``--preset 100m`` trains a ~100M-parameter private model.

``--checkpoint-dir`` snapshots the complete federation (client states,
PushSum weights, round counter, DP accountant steps) every
``--checkpoint-every`` rounds; ``--resume`` restarts a killed run from the
newest snapshot and replays the remaining rounds bit-identically to an
uninterrupted run (see ``repro.checkpoint``).

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
        --rounds 3 --steps-per-round 5
    PYTHONPATH=src python -m repro.launch.train --preset 100m --rounds 10
    PYTHONPATH=src python -m repro.launch.train --preset 100m --rounds 50 \
        --checkpoint-dir ckpts/run0 --checkpoint-every 5 --resume
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import FederationCheckpointer, config_fingerprint
from ..configs import list_archs, get_config
from ..configs.base import DPConfig, LayerSpec, ModelConfig, ProxyFLConfig
from ..configs.registry import proxy_of, smoke_variant
from ..core.accountant import PrivacyAccountant
from ..core.engine import FederationEngine, block_spans
from ..data.synthetic import make_lm_data
from ..nn.losses import cross_entropy
from ..nn.model import forward
from .compile_cache import use_compile_cache
from .steps import StepOptions, init_train_state, make_train_step


def preset_100m(vocab: int = 8192) -> ModelConfig:
    """~100M-parameter dense decoder for the end-to-end example."""
    return ModelConfig(
        name="repro-100m", arch_type="dense", vocab_size=vocab, d_model=768,
        n_layers=12, n_heads=12, n_kv_heads=12, head_dim=64, d_ff=3072,
        pattern=(LayerSpec(),), tie_embeddings=True,
        source="end-to-end driver preset")


def build_cfgs(args):
    if args.preset == "100m":
        cfg = preset_100m()
        proxy = proxy_of(cfg, n_layers=4, d_model=256)
    else:
        cfg = get_config(args.arch)
        if args.smoke:
            cfg = smoke_variant(cfg)
        proxy = smoke_variant(proxy_of(cfg)) if args.smoke else proxy_of(cfg)
    return cfg, proxy


@functools.lru_cache(maxsize=4)
def _ppl_loss(cfg: ModelConfig):
    """One jitted eval loss per config (a fresh jit per call would compile
    the private forward again at every block edge)."""
    @jax.jit
    @jax.named_scope("fl.eval")
    def loss(p, t):
        return cross_entropy(forward(p, cfg, t[:, :-1])[0], t[:, 1:])

    return loss


def evaluate_ppl(params, cfg: ModelConfig, tokens: jnp.ndarray, batch: int = 8
                 ) -> float:
    with jax.profiler.TraceAnnotation("fl.edge.eval"):
        losses = []
        fwd = _ppl_loss(cfg)
        for i in range(0, tokens.shape[0], batch):
            losses.append(float(fwd(params, tokens[i:i + batch])))
        return float(np.exp(np.mean(losses)))


def make_engine(cfg: ModelConfig, proxy: ModelConfig, fl: ProxyFLConfig,
                backend: str = "vmap", mesh=None) -> FederationEngine:
    """The federation this driver trains: ``fl.n_clients`` clients of
    private ``cfg`` + proxy ``proxy`` LM pairs, each step drawing
    ``fl.batch_size`` sequences from its client's ``[n, seq + 1]`` token
    array."""
    # remat: at the 100m preset 4 clients need 12.7 MB more than a 16 GB
    # v5e holds without it (tests/test_tpu_compile.py compiles this)
    opts = StepOptions(remat=True, accum=1, dp_chunk=fl.batch_size)

    def sample(toks, kb, n_valid=None):
        # masked-sampler protocol: ragged per-client corpora on the vmap
        # backend pass the true sequence count so padding is never drawn
        hi = toks.shape[0] if n_valid is None else n_valid
        idx = jax.random.randint(kb, (fl.batch_size,), 0, hi)
        return {"tokens": toks[idx, :-1], "labels": toks[idx, 1:]}

    return FederationEngine(
        fl, n_clients=fl.n_clients,
        step_fns=make_train_step(cfg, proxy, fl, opts),
        init_fns=lambda k2: init_train_state(k2, cfg, proxy, fl, opts),
        sample_fn=sample, backend=backend, mix="pushsum", mesh=mesh)


def main(argv=None) -> int:
    use_compile_cache()
    run(argv)
    return 0


def run(argv=None) -> Dict[str, Any]:
    """Parse ``argv`` as the command line, train, print one line per round,
    and return the run: ``engine``, final ``state``, the stacked per-round
    ``metrics`` of every block (``[rounds, K]`` each, rounds run by this
    call only) and ``block_seconds`` (device time of each block, compile
    included in the first)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--preset", choices=("100m",), default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family variant (CPU-friendly)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--steps-per-round", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--weight-decay", type=float, default=1e-4)
    ap.add_argument("--alpha", type=float, default=0.5)
    ap.add_argument("--no-dp", action="store_true")
    ap.add_argument("--sigma", type=float, default=1.0)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--topology", default="exponential",
                    choices=("exponential", "ring", "full"))
    ap.add_argument("--backend", default="vmap",
                    choices=("loop", "vmap", "shard_map", "async", "hier"),
                    help="federation engine backend (vmap = one compiled "
                         "round program; shard_map = one client per device "
                         "of the first --clients devices, ppermute "
                         "exchange; async = staleness-τ stale gossip, "
                         "see --staleness; hier = two-level cohort of "
                         "--n-shards shards with block-diagonal intra-shard "
                         "mixing and sparse cross-shard edges, see "
                         "--n-shards)")
    ap.add_argument("--staleness", type=int, default=0,
                    help="gossip delay τ for --backend async or hier: the "
                         "round-t exchange merges neighbor proxy mass sent "
                         "τ rounds earlier (communication overlapped with "
                         "the local scans); with hier only the CROSS-SHARD "
                         "edges are delayed; 0 is bit-identical to the "
                         "vmap backend")
    ap.add_argument("--n-shards", type=int, default=1,
                    help="two-level cohort layout for --backend hier: "
                         "n_shards shards of clients/n_shards clients each "
                         "(must divide evenly); 1 keeps every edge "
                         "intra-shard and runs the vmap round programs "
                         "verbatim")
    ap.add_argument("--dropout-rate", type=float, default=0.0,
                    help="per-round client dropout probability (§3.4)")
    ap.add_argument("--min-active", type=int, default=1,
                    help="floor on participating clients per round when "
                         "--dropout-rate > 0")
    ap.add_argument("--rounds-per-block", type=int, default=1,
                    help="rounds fused into one compiled engine round-block "
                         "(vmap backend: the host is re-entered only at "
                         "block edges; 1 = historical per-round execution; "
                         "any value is bit-identical, checkpoints land on "
                         "block edges)")
    ap.add_argument("--size-skew", type=float, default=0.0,
                    help="per-client corpus size skew in [0, 1): client k "
                         "holds ~64*(1-skew)^k sequences, a ragged cohort "
                         "that exercises the padded/masked vmap path")
    ap.add_argument("--use-pallas", action="store_true",
                    help="Pallas-fused round hot path: the PushSum exchange "
                         "runs as one blocked HBM->VMEM kernel pass (real "
                         "Mosaic kernels on TPU, interpret mode elsewhere); "
                         "allclose to the plain-XLA path. The LLM DP step "
                         "keeps its chunked XLA path — the fused DP "
                         "clip->noise->step applies to the classifier-scale "
                         "protocol steps (repro.core.protocol)")
    ap.add_argument("--compress", default="none",
                    choices=("none", "topk", "int8"),
                    help="compressed proxy exchange (repro.core.compress): "
                         "top-k sparsification or int8 stochastic-rounding "
                         "quantization of the DELTA against a public proxy "
                         "copy carried per client in the engine state "
                         "(error feedback — truncated mass is re-sent "
                         "later); 'none' keeps the exchange byte-for-byte "
                         "full-precision")
    ap.add_argument("--compress-ratio", type=float, default=0.25,
                    help="top-k kept fraction of the flattened proxy "
                         "(with --compress topk; 0.25 -> ~6.4x fewer "
                         "bytes on the wire)")
    ap.add_argument("--verify-commitments", action="store_true",
                    help="verifiable federation (repro.core.commit): check "
                         "every received proxy against its sender's "
                         "declared commitment before mixing (loop backend) "
                         "and restore checkpoints in strict commitment "
                         "mode — snapshots whose hash chain, leaf digests "
                         "or fingerprint records fail verification are "
                         "refused with the divergent round/leaf named")
    ap.add_argument("--checkpoint-dir", default=None,
                    help="snapshot complete federation state here (enables "
                         "preemption-tolerant runs; see repro.checkpoint)")
    ap.add_argument("--checkpoint-every", type=int, default=1,
                    help="rounds between snapshots (with --checkpoint-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="restart from the newest snapshot in "
                         "--checkpoint-dir (bit-identical continuation)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not args.preset and not args.arch:
        args.preset = "100m"

    cfg, proxy = build_cfgs(args)
    K = args.clients
    fl = ProxyFLConfig(
        alpha=args.alpha, beta=args.alpha, n_clients=K, rounds=args.rounds,
        local_steps=args.steps_per_round, lr=args.lr,
        weight_decay=args.weight_decay, batch_size=args.batch,
        topology=args.topology, seed=args.seed,
        dropout_rate=args.dropout_rate, min_active=args.min_active,
        staleness=args.staleness, n_shards=args.n_shards,
        use_pallas=args.use_pallas, compress=args.compress,
        compress_ratio=args.compress_ratio,
        verify_commitments=args.verify_commitments,
        dp=DPConfig(enabled=not args.no_dp, clip_norm=args.clip,
                    noise_multiplier=args.sigma))
    if args.staleness and args.backend not in ("async", "hier"):
        raise SystemExit("--staleness requires --backend async or hier "
                         "(the synchronous backends deliver every round)")
    if args.n_shards > 1 and args.backend != "hier":
        raise SystemExit("--n-shards > 1 requires --backend hier "
                         "(the flat backends have no shard level)")
    key = jax.random.PRNGKey(args.seed)
    print(f"[train] private={cfg.name} ({tree_size_of(cfg)} params approx: "
          f"{cfg.param_counts()['total']/1e6:.1f}M)  proxy={proxy.name} "
          f"({proxy.param_counts()['total']/1e6:.1f}M)  clients={K} "
          f"backend={args.backend}")

    # non-IID synthetic LM data: each client's stream comes from its own
    # bigram chain (domain = client id); the test stream mixes all domains.
    def lm_set(k2, n_seqs, domain):
        v = min(cfg.vocab_size, 2048)
        stream = make_lm_data(k2, n_seqs * (args.seq + 1), v, domain=domain)
        return stream.reshape(n_seqs, args.seq + 1)

    n_seqs = [max(args.batch, int(round(64 * (1.0 - args.size_skew) ** k)))
              for k in range(K)]
    data: List[jnp.ndarray] = [
        lm_set(jax.random.fold_in(key, 100 + k), n_seqs[k], domain=k)
        for k in range(K)]
    test = jnp.concatenate([
        lm_set(jax.random.fold_in(key, 999 + k), max(1, 32 // K), domain=k)
        for k in range(K)])

    mesh = None
    if args.backend == "shard_map":
        if jax.device_count() < K:
            raise SystemExit(f"--backend shard_map needs one device per "
                             f"client: {K} clients, {jax.device_count()} "
                             "devices")
        mesh = jax.make_mesh((K,), ("clients",), devices=jax.devices()[:K])
    engine = make_engine(cfg, proxy, fl, args.backend, mesh)
    if not args.no_dp:
        # DP sample rate q = B / n_local from each client's ACTUAL dataset
        # size (the accountant's subsampling amplification assumes this).
        engine.attach_accountants([
            PrivacyAccountant(args.sigma,
                              min(1.0, args.batch / data[k].shape[0]), 1e-5)
            for k in range(K)])

    ckpt, state, start = None, None, 0
    if args.checkpoint_dir:
        ckpt = FederationCheckpointer(
            args.checkpoint_dir, every=args.checkpoint_every,
            fingerprint=config_fingerprint(
                fl, arch=cfg.name, proxy=proxy.name, clients=K,
                # data-shaping flag: resuming under a different skew would
                # silently continue on a different cohort
                size_skew=args.size_skew),
            verify=fl.verify_commitments)
        if args.resume:
            # restored before any init: one client state on the device
            restored = ckpt.restore_latest(engine, base_key=key)
            if restored is not None:
                state, start = restored
                print(f"[train] resumed from {args.checkpoint_dir} at "
                      f"round {start}")
    if state is None:
        state = engine.init_states(key)

    # engine-owned round-blocks: up to --rounds-per-block rounds run as one
    # compiled program; the host syncs (checkpoint, ppl eval, logging) only
    # at block edges, and block_spans cuts blocks so every checkpoint-
    # cadence round IS a block edge — the snapshot set matches per-round
    # execution.
    rows: Dict[str, List[np.ndarray]] = {}
    block_seconds: List[float] = []
    for t, n_block in block_spans(start, args.rounds, args.rounds_per_block,
                                  ckpt.every if ckpt is not None else 0):
        t0 = time.perf_counter()
        state, metrics = engine.run_rounds(state, data, t, n_block, key)
        jax.block_until_ready(state)  # device time, not the enqueue
        dt = time.perf_counter() - t0
        block_seconds.append(dt)
        for name, v in metrics.items():
            rows.setdefault(name, []).append(v)
        if ckpt is not None:
            ckpt.maybe_save(engine, state, t + n_block - 1, base_key=key)
        ppl = evaluate_ppl(engine.client_params(state, 0, "private"), cfg, test)
        # worst case over clients: under --size-skew the smallest client has
        # the largest sample rate and spends epsilon fastest
        eps = max((a.epsilon() for a in engine.accountants if a is not None),
                  default=float("nan"))
        for i in range(n_block):
            n_active = int(np.sum(~np.isnan(metrics["private_loss"][i])))
            line = (f"[round {t+i+1}/{args.rounds}] "
                    f"private_loss={np.nanmean(metrics['private_loss'][i]):.4f} "
                    f"proxy_loss={np.nanmean(metrics['proxy_loss'][i]):.4f} "
                    f"active={n_active}/{K} ")
            if i == n_block - 1:  # block edge: host-synced ppl/eps/time
                line += f"client0_test_ppl={ppl:.2f} eps={eps:.3f} ({dt:.1f}s)"
            print(line)
    return {"engine": engine, "state": state, "block_seconds": block_seconds,
            "metrics": {k: np.concatenate(v) for k, v in rows.items()}}


def tree_size_of(cfg: ModelConfig) -> str:
    return f"{cfg.n_layers}L/d{cfg.d_model}"


if __name__ == "__main__":
    raise SystemExit(main())
