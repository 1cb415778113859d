"""FederationEngine — ONE executor for every federated round in the repo.

The paper's round structure (Algorithm 1: ``local_steps`` local updates per
client, then one exchange over a column-stochastic graph) is shared by every
method in the METHODS table — ProxyFL, FML, FedAvg, AvgPush, CWT, Regular —
and by the LLM-scale driver in ``launch/train.py``. This module owns that
round once, behind three selectable backends:

``loop``
    One Python iteration per client per step, each client's step jitted
    individually. The only backend that supports *heterogeneous private
    architectures* (paper Fig. 5b — every client may bring a different
    model; tree structures differ, so clients cannot be stacked). Gossip
    stacks the (shared-architecture) proxies host-side and applies P^(t)
    as one matmul — the original simulation semantics.

``vmap`` (default for homogeneous cohorts)
    Client states are stacked into one pytree with a leading K dim; the
    whole round is ONE compiled XLA program: ``jax.lax.scan`` fuses the
    ``local_steps`` loop, ``jax.vmap`` batches the K clients, and the
    PushSum exchange runs on-device as a [K,K]×[K,D] matmul on the stacked
    flattened proxies — no per-round ``tree_flatten_vector`` host
    round-trips and no O(K·steps) Python dispatch. P^(t), the active
    mask, per-client valid lengths and per-client step counts are runtime
    *arguments*, so all rounds reuse a single compilation.

    RAGGED cohorts (size-skewed non-IID partitions, e.g. Dirichlet —
    paper §4.3/4.4) run natively on this path: per-client datasets are
    padded to the cohort max and stacked (:func:`repro.data.ragged.pad_stack`),
    the sampler draws batch indices via ``randint(0, n_valid[k])`` so
    padding is never sampled, and in epoch mode (``local_steps == 0``)
    each client runs its OWN ``n_k // B`` steps: a per-step mask (composed
    with the §3.4 ``active`` mask) freezes a client's state and RNG chain
    once it has exhausted its local epoch, so it sits out the remaining
    scan iterations bit-exactly.

``shard_map``
    Same stacked round, but with one client per device of a mesh axis and
    the exchange realized as a ``jax.lax.ppermute`` collective
    (:func:`repro.core.gossip.pushsum_gossip_shard`) — the TPU-native
    O(1)-per-round communication path used at LLM scale. Requires a mesh
    whose ``axis`` has exactly ``n_clients`` devices. The round-t shift and
    the active pattern are trace-time static (each distinct membership
    pattern compiles its own collective schedule).

``async`` (stale gossip, Assran et al. 2019)
    The overlap-friendly fourth backend: instead of blocking on the
    in-neighbor's CURRENT proxy, round t's exchange delivers the proxy
    mass neighbors put in flight τ rounds earlier (``cfg.staleness``),
    modeling gossip overlapped with the next τ local scans — the
    synchronous protocol's straggler stall removed. Mechanically it is the
    vmap backend with the exchange split by
    :func:`repro.core.gossip.stale_mix_split`: each client KEEPS the
    diagonal of P^(t) applied to its raw PushSum numerator θ = z·w, SENDS
    the off-diagonal part into a τ-deep in-flight buffer, and MERGES the
    round-(t−τ) deliveries; de-biasing by the identically-delayed weights
    w keeps z a proper weighted average at every staleness, and total
    θ/w mass (clients + buffer) is conserved under arbitrary τ and §3.4
    dropout — see the stale-gossip note in ``repro.core.gossip``. The
    buffer is part of the engine state (``{"clients", "stale_theta",
    "stale_w"}``), travels through checkpoints, and rotates inside the
    round-block scan, so any block size and any kill/resume replays the
    identical trajectory bit-for-bit. τ=0 means immediate delivery: the
    engine then runs the vmap round program VERBATIM (same compiled
    program, unwrapped state), so ``staleness=0`` is bit-identical to
    ``backend="vmap"`` — params and epsilon — by construction (enforced
    by tests/test_conformance.py). Local-step RNG, batch draws and the DP
    accountant schedule are untouched by τ (staleness delays delivery,
    never compute), so epsilon is independent of τ. Semantics notes:
    inactive (§3.4) clients run no local steps and send nothing, but
    in-flight mass addressed to them still arrives (a mailbox merge —
    dropping it would destroy PushSum mass); the pure-permutation
    ``ring`` mix (CWT) keeps no self mass, so τ>0 would leave clients
    model-less for τ rounds — rejected at construction.

``hier`` (two-level hierarchical gossip)
    The thousand-client composition of the three stacked backends: a
    two-level cohort of ``cfg.n_shards × clients_per_shard`` (K must
    divide evenly) where intra-shard exchange is the on-device matmul mix
    over the stacked shard-local params (the vmap machinery —
    ``fused_pushsum_mix``-eligible under ``use_pallas``, vmapped over the
    shard axis) and inter-shard exchange is a sparse scaled permutation
    (at most ONE cross-shard edge per client per round — exactly the
    structure a ``ppermute`` collective realizes on a real device mesh;
    see ``launch/steps.py``/``launch/dryrun.py --program hier_block`` for
    the mesh deployment). Crucially this is NOT a different protocol:
    hier executes the SAME flat column-stochastic schedule P^(t) as vmap,
    FACTORED by edge locality (:func:`repro.core.gossip.hier_mix_split`:
    P = blockdiag[S, L, L] + cross scaled partial permutation — an exact
    sum decomposition), so ``n_shards`` is a pure execution-layout
    parameter at τ=0: the factored application is bit-identical to the
    dense [K, K] matmul (each output row performs the same ≤2 real
    additions), at O(K·L·D) + O(K·D) FLOPs instead of O(K²·D). With
    ``staleness`` τ>0 the cross-shard edges — and ONLY those — deliver
    through the async τ-deep in-flight buffer (``{"hier_buffer":
    [τ, K, D], "hier_w": [τ, K]}`` in the engine state, riding the
    block-scan carry and every checkpoint) while the intra-shard exchange
    stays synchronous: the deployment model is pods gossiping locally
    every round while inter-pod traffic hides behind τ rounds of compute.
    Mass conservation (clients + buffer) holds for any (n_shards, τ,
    dropout) — :func:`repro.core.gossip.hier_gossip_reference` is the
    executable spec. Checkpoints stay backend-portable: client states
    keep the FLAT [K, ...] vmap layout (the shard reshape happens only
    inside the traced programs), so a hier snapshot restores into
    loop/vmap engines unchanged; only the τ>0 buffer keys are
    hier-specific (a τ-mismatched restore fails the shape match, and the
    config fingerprint covers ``n_shards``). ``n_shards=1`` (any τ:
    every edge is intra-shard, so staleness is vacuous) and τ=0 S>1 run
    bit-identically to ``backend="vmap"`` — params AND epsilon — the
    former literally via the vmap round programs, the latter via the
    factored-application bit-equality (both enforced by
    tests/test_conformance.py). Dense mixing (``mix="mean"`` /
    ``topology="full"``) has O(K) cross edges per client — no O(1)
    collective schedule exists — and is rejected at construction for
    S>1, as is the pure-permutation ring mix with τ>0 (same model-less
    argument as async) and compressed exchange (the codec is wired to
    the dense matmul paths; factored compressed gossip is future work).

Backend selection guide
-----------------------
* heterogeneous private models            -> ``loop`` (forced)
* homogeneous cohort, one host            -> ``vmap``
* one client per device/pod on a mesh     -> ``shard_map``
* straggler-tolerant stale gossip         -> ``async`` (+ ``staleness``)
* two-level cohort (pods × local clients) -> ``hier`` (+ ``cfg.n_shards``,
  optional ``staleness`` on the cross-shard edges)
* ``"auto"``                              -> ``vmap`` when client states
  share one tree structure and the per-client data trees are
  *pad-compatible* (same structure, dtypes and trailing dims; leading
  example counts may differ — raggedness is handled by padding + masked
  sampling), otherwise ``loop``. Only genuinely incompatible trees fall
  back to the O(K·steps) Python loop. Caveat: in epoch mode
  (``local_steps == 0``) the stacked scan runs the cohort-MAX step count
  with exhausted clients masked, so at high size skew the loop backend's
  exact ``sum(n_k // B)`` steps can be cheaper (CPU especially) — pass
  ``backend="loop"`` explicitly there; ``benchmarks/fig_ragged.py``
  quantifies the tradeoff per regime.

Exchange rules (``mix``) are column-stochastic matrices built by
:func:`repro.core.gossip.mix_matrix`: ``"pushsum"`` (ProxyFL/AvgPush),
``"mean"`` (FedAvg/FML), ``"ring"`` (CWT), ``"none"`` (Regular/Joint).

Round-blocks (fused multi-round execution)
------------------------------------------
The ENGINE owns the round boundary, not the caller. ``run_round`` executes
one round; :meth:`FederationEngine.run_rounds` executes a whole block of
``n_rounds`` with the host re-entered only at the block edge. On the vmap
backend the block is ONE compiled XLA program — an outer ``lax.scan`` over
rounds wrapped around the per-round scan/vmap body, consuming the block's
exchange matrices as a single stacked ``[T, K, K]`` runtime argument
(:func:`repro.core.gossip.mix_schedule`) and folding each round's RNG key
in-scan (``round_key``; the per-round schedule is replayed bit-exactly, so
ANY block size produces bit-identical parameters and epsilon). shard_map
blocks unroll the per-round collective schedules inside one jit; the loop
backend keeps genuine per-round semantics as the bit-identity reference.

Block EDGES are the protocol's host-visible boundary: checkpoints are
written there (a kill/resume lands on an edge and replays bit-identically
— drivers cut blocks so every checkpoint/eval cadence round IS an edge),
evaluation and history rows read there, DP accountants bulk-step there
(``PrivacyAccountant.step(n)``), and §3.4 join/leave membership is
resolved there for the whole block (``active_schedule``). This is the
prerequisite for the planned ASYNC fourth backend: overlap-friendly
variants (clients gossiping stale proxies while the next local scan runs,
Assran et al.) need the engine — not the caller — to own a multi-round
horizon inside which rounds may interleave, while the block edge stays
the only point where external observers (checkpointer, evaluator,
membership changes) interact with the federation. The ``async`` backend
is exactly that fourth backend: rounds interleave INSIDE a block through
the τ-deep in-flight buffer carried in the block scan's state, while the
block edge stays the only host-visible boundary — the buffer is snapshot
and restored there, so kill/resume stays bit-identical at any τ. When is
τ>0 accuracy-safe? ``benchmarks/fig_async.py`` measures final proxy
accuracy and rounds/sec vs τ ∈ {0, 1, 2, 4}: private accuracy is
unaffected at any τ (the local DML schedule is untouched — only delivery
is delayed), and small staleness (τ ≤ 2) reaches the synchronous
reference's proxy accuracy given a modestly longer horizon (measured:
equal at 40 rounds on the synthetic MNIST task, where the sync run
converges by ~30), while large τ (≥ 4) visibly slows consensus — mix
information is τ rounds old — and needs proportionally more rounds.

Dropout/join (paper §3.4): every backend threads an ``active`` bool mask
through the round — inactive clients run no local steps, keep their state,
and the time-varying graph re-knits itself over the active subset (mass
conservation and de-biased convergence to the ACTIVE average are
preserved). Set ``ProxyFLConfig.dropout_rate`` for a deterministic
per-round schedule, or pass ``active=`` explicitly to ``run_round``.

Fused hot path (Pallas)
-----------------------
With ``ProxyFLConfig.use_pallas`` the two chains that dominate a round's
HBM traffic each touch every parameter chunk ONCE:

* the PushSum exchange — the matmul-mix backends (loop/vmap/async, both
  per-round and round-block programs) route through
  :func:`repro.core.gossip.pushsum_mix_debiased` /
  :func:`repro.core.gossip.stale_mix_apply`, whose fused kernels
  (``repro.kernels.pushsum_mix``) keep the small [K,K] exchange matrix
  resident in VMEM and stream the stacked [K, D] proxies block-by-block,
  computing mix + de-bias (and for the stale τ>0 split: re-bias, kept/sent
  split, buffer merge, de-bias) in one HBM→VMEM pass per chunk instead of
  XLA's materialized matmul → divide chain;
* the DP proxy update — ``cfg.dp.enabled`` steps go through
  :func:`repro.core.dp.dp_adam_update`, fusing per-microbatch clip→
  accumulate (``repro.kernels.dp_clip``) and the trailing noise→Adam step
  (``repro.kernels.dp_step``) over the flattened gradient vector.

Dispatch is platform-aware (``repro.kernels.default_interpret``): real
Mosaic kernels on TPU, interpret mode elsewhere. The fused path is
allclose — not bit-identical — to the plain-XLA reference (f32
accumulation, fused reduction order); tests/test_conformance.py pins the
parity (params AND epsilon) per backend, and ``benchmarks/fig_kernels.py``
measures the rounds/sec and bytes-moved-per-round effect. shard_map keeps
its ppermute collective exchange regardless of the flag.

Compressed proxy exchange (error feedback)
------------------------------------------
``ProxyFLConfig.compress`` ∈ {"none", "topk", "int8"} (plus
``compress_ratio`` for top-k) routes every matmul-mix exchange through
``repro.core.compress``: the off-diagonal transmissions — and ONLY those;
a client's kept mass never crosses the wire — are sparsified/quantized,
and each client carries its codec state — the PUBLIC COPY ``ẑ_k`` [D]
every receiver already holds — in the ENGINE state (the same carried-
state pattern as the async τ-buffer: a federation-level ``{"clients",
"ef_state"}`` wrapper, never inside the per-client trees a step_fn could
drop). The wire carries compressed DELTAS against that copy
(CHOCO-SGD-style): ``c_k = C(m_k − ẑ_k)``, ``ẑ'_k = ẑ_k + c_k``, and
receivers mix the DENSE ``ẑ'_k`` — so sparsification never zero-fills a
coordinate on the receiver and the de-bias denominator stays exact. The
error-feedback residual is implicit (``m_k − ẑ'_k``): per transmitting
client per round ``c_k + (m_k − ẑ'_k) == m_k − ẑ_k`` exactly in f32, so
truncated mass is DELAYED into later rounds, never destroyed; clients
that send nothing (§3.4 dropouts, no-exchange rounds) keep their public
copy untouched. The copies warm-start at the initial proxies (one
uncompressed setup broadcast). The codec state rides the block-scan
carry (any block size replays bit-identically), travels through
``_ckpt_payload``/``restore_state`` (kill/
resume is bit-identical; config fingerprints refuse a compression-config
mismatch), and de-bias weights are never compressed, so PushSum w-mass
conservation is exact at any τ. ``compress="none"`` keeps every round
program byte-for-byte the uncompressed one (enforced bitwise by
tests/test_conformance.py). Compression composes with loop/vmap/blocked/
async-τ>0; shard_map's ppermute exchange is uncompressed-only (rejected
at construction), and ``use_pallas`` falls back to the plain-XLA path for
the exchange while compressing (the fused kernels implement the
uncompressed chain — see ``repro.core.compress``).

Typical usage::

    engine = dml_engine((spec,) * K, proxy_spec, cfg)   # backend="auto"
    state = engine.init_states(jax.random.PRNGKey(0))
    for t in range(cfg.rounds):                         # per-round driving
        state, metrics = engine.run_round(
            state, client_data, t, round_key(key, t))
    # ... or hand the engine a whole fused horizon (same bits, one program):
    state, metrics = engine.run_rounds(state, client_data, 0, cfg.rounds, key)
    params_k = engine.client_params(state, k, role="private")

The per-client state is a pytree dict with (at least) ``{"proxy":
{"params", "opt"}, "w"}``; the engine gossips ``proxy.params`` and the
PushSum weight ``w`` and leaves everything else (private model, optimizer
moments, step counters) client-local — exactly the paper's privacy
boundary: only proxies ever cross clients.

The conventions this module depends on — the canonical ``round_key``
schedule, checkpoint coverage of every scan-carry key, config
fingerprinting, trace hygiene in the round cores — are machine-checked
contracts: ``docs/INVARIANTS.md`` documents them, ``tools/fedlint``
enforces them in CI (``scripts/ci.sh --lint``). Extending the engine
state or the RNG schedule means extending those tables in the same PR.
"""
from __future__ import annotations

import functools
import inspect
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint.ckpt import load_checkpoint, save_checkpoint
from ..configs.base import ProxyFLConfig
from ..data.ragged import pad_compatible, pad_stack
from ..nn.modules import tree_flatten_vector, tree_unflatten_vector
from ..optim import Adam
from .compress import compress_round_key, compress_spec
from .gossip import (gossip_shift, hier_layout, hier_mix_debiased,
                     hier_mix_schedule, hier_mix_split,
                     hier_stale_mix_apply, mix_matrix, mix_schedule,
                     pushsum_gossip_shard, pushsum_mix_debiased,
                     shift_schedule, stale_mix_apply,
                     stale_mix_schedule, stale_mix_split)

BACKENDS = ("loop", "vmap", "shard_map", "async", "hier")
MIXES = ("pushsum", "mean", "ring", "none")

# round t's RNG key is fold_in(base_key, ROUND_KEY_OFFSET + t) — the
# historical schedule every driver used; round-blocks fold it IN-SCAN so a
# blocked run replays the identical per-round keys bit-exactly.
ROUND_KEY_OFFSET = 10_000

StepFn = Callable[[Dict, Any, jnp.ndarray], Tuple[Dict, Dict]]
InitFn = Callable[[jnp.ndarray], Dict]
SampleFn = Callable[[Any, jnp.ndarray], Any]


def _sampler_accepts_n_valid(fn) -> bool:
    """True when ``fn`` can be called ``fn(data_k, key, n_valid=...)`` —
    the masked-sampling protocol ragged cohorts need on the stacked path
    (``n_valid`` bounds the index draw so padding is never sampled). The
    parameter must be NAMED ``n_valid``: bare third-argument sniffing
    would silently feed the dataset length into an unrelated parameter of
    a legacy 3-arg sampler. Samplers without it stay supported for
    rectangular data."""
    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):  # builtins / C callables: be conservative
        return False
    p = sig.parameters.get("n_valid")
    return p is not None and p.kind in (p.POSITIONAL_OR_KEYWORD,
                                        p.KEYWORD_ONLY)


def round_key(base_key, t):
    """Round t's RNG key under the engine's canonical schedule."""
    return jax.random.fold_in(base_key, ROUND_KEY_OFFSET + t)


def active_mask(t: int, n_clients: int, cfg: ProxyFLConfig
                ) -> Optional[np.ndarray]:
    """Deterministic per-round §3.4 dropout schedule from the config.

    Returns None (everyone participates) when ``cfg.dropout_rate == 0``;
    otherwise a bool[K] mask drawn from a seed derived from (cfg.seed, t),
    re-sampled identically by every backend and across reruns."""
    if not cfg.dropout_rate:
        return None
    rng = np.random.default_rng(np.random.SeedSequence([cfg.seed, 7919, t]))
    act = rng.random(n_clients) >= cfg.dropout_rate
    floor = max(1, min(cfg.min_active, n_clients))
    if act.sum() < floor:
        act[rng.choice(n_clients, size=floor, replace=False)] = True
    return act


def block_spans(start: int, rounds: int, rounds_per_block: int, *cadences):
    """Yield ``(t0, n)`` round-block spans covering ``[start, rounds)``.

    Blocks are at most ``rounds_per_block`` long and are CUT so that every
    multiple of each nonzero cadence (checkpoint_every, eval_every, ...)
    lands exactly on a block edge — the one place drivers may observe the
    federation. This is the single definition of the block-cutting rule;
    both ``baselines._drive_blocks`` and ``launch/train.py`` iterate it,
    so the "cadence rounds are block edges" invariant cannot drift."""
    B = max(1, int(rounds_per_block or 1))
    t = start
    while t < rounds:
        n = min(B, rounds - t)
        for c in cadences:
            if c and c > 0:
                n = min(n, c - t % c)
        yield t, n
        t += n


def active_schedule(t0: int, n_rounds: int, n_clients: int,
                    cfg: ProxyFLConfig) -> Optional[np.ndarray]:
    """Block-level §3.4 membership: ``active_mask`` for each round of a
    block, stacked to bool[T, K]. None when no dropout is configured (the
    per-t masks are all None). The per-round draws are preserved exactly
    (seeded per (cfg.seed, t)), so a blocked run replays the identical
    dropout trajectory as the per-round path."""
    masks = [active_mask(t, n_clients, cfg)
             for t in range(t0, t0 + n_rounds)]
    if all(m is None for m in masks):
        return None
    return np.stack([np.ones(n_clients, bool) if m is None else m
                     for m in masks])


def stack_states(states: Sequence[Dict]) -> Dict:
    """List of per-client state pytrees -> one pytree with leading K dim."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *states)


def unstack_state(stacked: Dict, k: int) -> Dict:
    return jax.tree_util.tree_map(lambda x: x[k], stacked)


def _tree_where(mask_k: jnp.ndarray, new: Dict, old: Dict) -> Dict:
    """Per-client select over stacked pytrees (mask_k: bool[K])."""
    def sel(n, o):
        m = mask_k.reshape((mask_k.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(sel, new, old)


def _stack_metric_rows(rows: Sequence[Dict[str, np.ndarray]], n_clients: int
                       ) -> Dict[str, np.ndarray]:
    """Per-round metric dicts ([K] arrays) -> one [T, K] array per key
    (key union, NaN where a round didn't emit that metric)."""
    keys = set().union(*(r.keys() for r in rows)) if rows else set()
    nan = np.full(n_clients, np.nan)
    return {k: np.stack([np.asarray(r.get(k, nan), float) for r in rows])
            for k in sorted(keys)}


def _key_data(key) -> np.ndarray:
    """Raw uint32 words of a PRNG key (old-style arrays and typed keys);
    zeros stand for 'no key recorded' in checkpoints."""
    if key is None:
        return np.zeros((2,), np.uint32)
    if hasattr(key, "dtype") and jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return np.asarray(key, np.uint32)


class FederationEngine:
    """Multi-backend executor of one federated round (see module docstring).

    Parameters
    ----------
    cfg : ProxyFLConfig
        Protocol knobs (local_steps, batch_size, topology, dropout_rate...).
    n_clients : int
    step_fns : StepFn | Sequence[StepFn]
        ``step(state, batch, key) -> (state, metrics)`` — one client's local
        update. A sequence (len K) is allowed for the loop backend only
        (heterogeneous architectures).
    init_fns : InitFn | Sequence[InitFn]
        ``init(key) -> state`` per client.
    sample_fn : SampleFn
        ``sample(client_data, key) -> batch`` — draws one local batch.
    backend : "auto" | "loop" | "vmap" | "shard_map" | "async" | "hier"
    mix : "pushsum" | "mean" | "ring" | "none"
    mesh, axis : mesh + axis name for the shard_map backend.
    staleness : gossip delay τ for the async backend, and the CROSS-SHARD
        delay for the hier backend (None -> the value in
        ``cfg.staleness``); ignored by the synchronous backends. The hier
        shard count comes from ``cfg.n_shards`` (must divide n_clients).
    """

    def __init__(self, cfg: ProxyFLConfig, *, n_clients: int,
                 step_fns, init_fns, sample_fn: SampleFn,
                 backend: str = "auto", mix: str = "pushsum",
                 mesh=None, axis: str = "clients", staleness=None):
        assert mix in MIXES, mix
        self.cfg = cfg
        self.K = n_clients
        self.step_fns = (list(step_fns) if isinstance(step_fns, (list, tuple))
                         else [step_fns] * n_clients)
        self.init_fns = (list(init_fns) if isinstance(init_fns, (list, tuple))
                         else [init_fns] * n_clients)
        assert len(self.step_fns) == n_clients
        self.sample_fn = sample_fn
        self.mix = mix
        if mesh is not None:
            # GSPMD-propagated placement: the local phase vmaps unsharded
            # per-round keys against the client-sharded state, which an
            # Explicit-typed mesh (jax.make_mesh's default) refuses
            mesh = jax.sharding.Mesh(
                mesh.devices, mesh.axis_names,
                axis_types=(jax.sharding.AxisType.Auto,) * len(
                    mesh.axis_names))
        self.mesh = mesh
        self.axis = axis
        self.accountants: List = [None] * n_clients
        homogeneous = all(f is self.step_fns[0] for f in self.step_fns)
        if backend == "auto":
            backend = "vmap" if homogeneous else "loop"
        assert backend in BACKENDS, backend
        if backend in ("vmap", "shard_map", "async", "hier"):
            assert homogeneous, (
                f"{backend} backend requires a homogeneous cohort; "
                "heterogeneous private architectures need backend='loop'")
        if backend == "shard_map":
            assert mesh is not None, "shard_map backend needs a mesh"
            assert dict(mesh.shape).get(axis) == n_clients, (
                f"mesh axis {axis!r} must hold exactly {n_clients} devices")
        if backend in ("async", "hier"):
            self.staleness = int(cfg.staleness if staleness is None
                                 else staleness)
            assert self.staleness >= 0, self.staleness
            if self.staleness and mix == "ring":
                raise ValueError(
                    f"{backend} staleness>0 is incompatible with the pure-"
                    "permutation ring mix (CWT): clients keep no self mass, "
                    "so a delayed delivery would leave them model-less for "
                    "the first τ rounds; use staleness=0 or a mix with a "
                    "positive diagonal (pushsum/mean)")
        else:
            self.staleness = 0
        # staleness=0 is synchronous delivery: the async backend then runs
        # the vmap round programs verbatim on UNWRAPPED state (no buffer),
        # which is what makes τ=0 bit-identical to backend="vmap"
        self._stale = backend == "async" and self.staleness > 0
        # hier: two-level [n_shards × clients-per-shard] cohort executing
        # the SAME flat P^(t) factored by edge locality; n_shards=1 makes
        # every edge intra-shard (staleness vacuous), so the engine runs
        # the vmap round programs verbatim — the bit-identity anchor
        self.n_shards = (hier_layout(n_clients, cfg.n_shards)[0]
                         if backend == "hier" else 1)
        self._hier = backend == "hier" and self.n_shards > 1
        if self._hier and mix != "none" and n_clients > 1:
            topo = {"pushsum": cfg.topology, "mean": "full",
                    "ring": "ring"}[mix]
            if topo == "full":
                raise ValueError(
                    "hier with n_shards>1 needs a sparse exchange: dense "
                    "mixing (mix='mean' / topology='full') has O(K) "
                    "cross-shard edges per client, which no O(1) inter-"
                    "shard collective schedule can realize; use pushsum/"
                    "ring mixes or n_shards=1")
        self._hier_stale = self._hier and self.staleness > 0
        # compressed proxy exchange (cfg.compress): None keeps every round
        # program byte-for-byte the uncompressed one; a spec adds each
        # client's codec state (the public copy receivers mix) to the
        # engine state and routes the matmul exchanges through
        # repro.core.compress
        self.compress = compress_spec(cfg)
        if self.compress is not None and backend == "shard_map":
            raise ValueError(
                "compressed gossip (cfg.compress != 'none') is not "
                "implemented for the shard_map ppermute exchange — the "
                "collective ships full-precision tensors; use the loop/"
                "vmap/async backends for compressed rounds")
        if self.compress is not None and self._hier:
            raise ValueError(
                "compressed gossip (cfg.compress != 'none') is not "
                "implemented for the hier factored exchange — the codec "
                "is wired to the dense matmul paths; use n_shards=1 (which "
                "runs the vmap programs verbatim) or the loop/vmap/async "
                "backends for compressed rounds")
        self._compressed = (self.compress is not None
                            and mix != "none" and n_clients > 1)
        # a federation-level state wrapper {"clients": ..., [stale buffer,]
        # [codec public copies]} carries cross-round exchange state NEXT TO
        # the clients — per-client step_fns must never see (and drop) it
        self._wrapped = self._stale or self._compressed or self._hier_stale
        self.backend = backend
        # Pallas-fused exchange (cfg.use_pallas): the matmul-mix backends
        # route through the fused blocked kernels in repro.kernels —
        # allclose, not bit-identical, to the plain-XLA reference (f32
        # accumulation, fused de-bias). shard_map keeps its ppermute path.
        self.use_pallas = bool(getattr(cfg, "use_pallas", False))
        # Commitment verification of the received proxies (loop backend;
        # cfg.verify_commitments): each sender's released proxy is
        # committed to (repro.core.commit.client_commitment) before the
        # exchange and every receiver recomputes the digest from the wire
        # payload before mixing — a tampered in-flight proxy refuses with
        # a CommitmentError naming the client and round. transmit_tamper
        # is the adversary hook the byzantine tests inject (host-side
        # (flat [K, D] numpy, t) -> flat, e.g. attacks.bitflip_proxy);
        # None leaves the exchange untouched.
        self.verify_commitments = bool(getattr(cfg, "verify_commitments",
                                               False))
        self.transmit_tamper: Optional[Callable] = None
        self._masked_sampler = _sampler_accepts_n_valid(sample_fn)
        self._loop_steps: Dict = {}   # id(step_fn) -> jitted one-step
        self._rounds: Dict = {}       # compile cache: key -> jitted round
        # small keyed LRU: id(data) -> (ref, stacked, n_valid). A single
        # entry thrashes when two datasets alternate (train/finetune
        # interleave) — every round would re-pad, re-stack and re-transfer.
        self._data_cache: "OrderedDict" = OrderedDict()
        self._data_cache_max = 4
        self._stack_misses = 0        # observability: cache-miss count

    # -- state construction / access ---------------------------------------

    def _place(self, stacked, pin=jax.device_put):
        """The shard_map backend keeps client k's slice of every stacked
        [K, ...] leaf on device k of the mesh axis; without this the stack
        sits on the default device and every chip runs every client. The
        other backends leave placement to JAX. ``pin`` is ``device_put``
        for arrays and ``with_sharding_constraint`` inside a round program,
        whose output state stays split the same way."""
        if self.backend != "shard_map" or stacked is None:
            return stacked
        return pin(stacked, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(self.axis)))

    def _clients_of(self, state):
        """The per-client state tree (stacked pytree, or a list on the loop
        backend). For the stale async backend (τ>0) and for compressed
        exchanges the engine state is a federation-level wrapper
        ``{"clients": <stacked tree | list>, ["stale_theta": [τ, K, D],
        "stale_w": [τ, K],] ["hier_buffer": [τ, K, D], "hier_w": [τ, K],]
        ["ef_state": [K, D]]}`` — the in-flight gossip buffers (flat async
        or hier cross-shard) and the codec's public copies ride next to
        the clients, never inside them (per-client step_fns must not see
        or drop them)."""
        return state["clients"] if self._wrapped else state

    def init_states(self, key) -> Any:
        """Per-client init at fold_in(key, k) — identical across backends.
        The stale async backend additionally allocates the empty τ-deep
        in-flight buffer (cold start: nothing arrives for τ rounds and the
        de-bias weights account for the mass in flight); compressed
        exchanges WARM-START the public copies at the initial proxies
        (f32 [K, D] — accumulator precision regardless of the proxy
        dtype): one uncompressed broadcast at setup, after which every
        round's wire carries only the compressed delta — without it the
        copies need ≈1/ratio rounds to even cover the coordinates and
        the top-k proxies measurably lag at short horizons."""
        states = [self.init_fns[k](jax.random.fold_in(key, k))
                  for k in range(self.K)]
        base: Any = (states if self.backend == "loop"
                     else self._place(stack_states(states)))
        if not self._wrapped:
            return base
        state: Dict[str, Any] = {"clients": base}
        flat0 = tree_flatten_vector(states[0]["proxy"]["params"])
        if self._stale:
            state["stale_theta"] = jnp.zeros(
                (self.staleness, self.K, flat0.shape[0]), flat0.dtype)
            state["stale_w"] = jnp.zeros(
                (self.staleness, self.K),
                jnp.result_type(states[0]["w"]))
        if self._hier_stale:
            # cross-shard in-flight buffer (raw numerators θ = z·w + the
            # matching weights), cold-started empty: for τ rounds the
            # cross edges deliver nothing and the de-bias weights account
            # for the mass in flight — intra-shard mass is never buffered
            state["hier_buffer"] = jnp.zeros(
                (self.staleness, self.K, flat0.shape[0]), flat0.dtype)
            state["hier_w"] = jnp.zeros(
                (self.staleness, self.K),
                jnp.result_type(states[0]["w"]))
        if self._compressed:
            state["ef_state"] = jnp.stack(
                [tree_flatten_vector(s["proxy"]["params"])
                 for s in states]).astype(jnp.float32)
        return state

    def export_states(self, state) -> List[Dict]:
        clients = self._clients_of(state)
        if self.backend == "loop":
            return list(clients)
        return [unstack_state(clients, k) for k in range(self.K)]

    def client_state(self, state, k: int) -> Dict:
        clients = self._clients_of(state)
        return (clients[k] if self.backend == "loop"
                else unstack_state(clients, k))

    def client_params(self, state, k: int, role: str = "proxy"):
        clients = self._clients_of(state)
        s = clients[k] if self.backend == "loop" else clients
        p = s[role]["params"]
        return p if self.backend == "loop" else jax.tree_util.tree_map(
            lambda x: x[k], p)

    def stacked_params(self, state, role: str = "proxy"):
        """The whole cohort's ``role`` params with a leading K dim — the
        input batched evaluation wants. Free on the stacked backends (that
        IS the state layout); the loop backend stacks on demand, or returns
        None when the per-client trees differ (heterogeneous architectures
        cannot be batched — callers fall back to per-client evaluation)."""
        if self.backend != "loop":
            return self._clients_of(state)[role]["params"]
        trees = [s[role]["params"] for s in self._clients_of(state)]
        structs = {jax.tree_util.tree_structure(tr) for tr in trees}
        shapes = {tuple((x.shape, jnp.result_type(x))
                        for x in jax.tree_util.tree_leaves(tr))
                  for tr in trees}
        if len(structs) != 1 or len(shapes) != 1:
            return None
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)

    def attach_accountants(self, accountants: Sequence) -> None:
        assert len(accountants) == self.K
        self.accountants = list(accountants)

    # -- checkpointing -------------------------------------------------------

    def _ckpt_payload(self, state, t: int, base_key) -> Dict:
        """Backend-portable snapshot tree: per-client states (stacked
        vmap/shard_map state is gathered off the device mesh by the
        per-client unstack), the round counter, per-client accountant step
        counts, and the base RNG key the round keys derive from. The same
        builder produces the restore template, so save and restore always
        agree on tree structure. The state is copied to the host first:
        slicing it per client on the device would hold a second copy of
        every client's state there."""
        state = jax.device_get(state)
        clients = {f"c{k:04d}": s
                   for k, s in enumerate(self.export_states(state))}
        steps = np.asarray([a.steps if a is not None else 0
                            for a in self.accountants], np.int32)
        payload = {"clients": clients,
                   "rounds_done": np.asarray(t + 1, np.int32),
                   "accountant_steps": steps,
                   "base_key": _key_data(base_key),
                   # explicit flag: PRNGKey(0)'s key data is all zeros, so
                   # the key words alone cannot mean "no key recorded"
                   "base_key_set": np.asarray(base_key is not None, np.uint8)}
        if self._stale:
            # the in-flight gossip buffer is federation state: rounds
            # t+1..t+τ deliver sends recorded here, so a resume without it
            # could not replay the trajectory (a τ-mismatched or sync
            # checkpoint fails the key/shape match with a descriptive error)
            payload["stale_theta"] = state["stale_theta"]
            payload["stale_w"] = state["stale_w"]
        if self._hier_stale:
            # same argument for the hier cross-shard buffer: rounds
            # t+1..t+τ merge the cross-shard deliveries recorded here, so
            # a resume without it could not replay the trajectory (τ=0 /
            # n_shards=1 snapshots carry no buffer and stay plain vmap
            # payloads — backend-portable by construction)
            payload["hier_buffer"] = state["hier_buffer"]
            payload["hier_w"] = state["hier_w"]
        if self._compressed:
            # the codec's public copies are federation state for the same
            # reason: round t+1's transmission is C(m − ef_state) and the
            # receivers mix ef_state itself, so a resume without it (or
            # across a compression-config change — also refused by
            # FederationCheckpointer's config fingerprint) could not
            # replay the trajectory bit-identically
            payload["compress_ef_state"] = state["ef_state"]
        return payload

    def save_state(self, path: str, state, t: int, base_key=None) -> str:
        """Write a complete-federation snapshot after completed round ``t``
        (works on all backends; see ``repro.checkpoint.federation``)."""
        save_checkpoint(path, self._ckpt_payload(state, t, base_key))
        return path

    def restore_state(self, path: str, like=None, base_key=None
                      ) -> Tuple[Any, int]:
        """Bit-exact inverse of :meth:`save_state`; returns ``(state,
        rounds_done)`` in THIS engine's layout (a loop-backend checkpoint
        restores fine into a vmap engine and vice versa). ``like`` is a
        template state with the target tree structure (default: the shapes
        of ``init_states``; only shapes and dtypes are read, through host
        zeros whose pages are never touched). The snapshot is read to the
        host and stacked onto the device leaf by leaf, so the device never
        holds more than the restored state. Attached accountants get their
        step counters back; passing the run's ``base_key`` verifies the
        checkpoint was written under the same key schedule."""
        if like is None:
            like = jax.eval_shape(self.init_states, jax.random.PRNGKey(0))
        template = jax.tree_util.tree_map(
            lambda x: np.zeros(np.shape(x), x.dtype), like)
        loaded = load_checkpoint(path, self._ckpt_payload(template, 0, None))
        clients = [loaded["clients"][f"c{k:04d}"] for k in range(self.K)]
        base: Any = (jax.tree_util.tree_map(jnp.asarray, clients)
                     if self.backend == "loop"
                     else self._place(stack_states(clients)))
        if self._wrapped:
            state: Any = {"clients": base}
            if self._stale:
                state["stale_theta"] = jnp.asarray(loaded["stale_theta"])
                state["stale_w"] = jnp.asarray(loaded["stale_w"])
            if self._hier_stale:
                state["hier_buffer"] = jnp.asarray(loaded["hier_buffer"])
                state["hier_w"] = jnp.asarray(loaded["hier_w"])
            if self._compressed:
                state["ef_state"] = jnp.asarray(loaded["compress_ef_state"])
        else:
            state = base
        rounds_done = int(loaded["rounds_done"])
        steps = np.asarray(loaded["accountant_steps"])
        for k, acc in enumerate(self.accountants):
            if acc is not None:
                acc.steps = int(steps[k])
        saved_key = np.asarray(loaded["base_key"], np.uint32)
        if base_key is not None and bool(loaded["base_key_set"]) and \
                not np.array_equal(saved_key, _key_data(base_key)):
            raise ValueError(
                f"checkpoint {path!r} was written under a different base RNG "
                "key; resuming would change the round key schedule")
        return state, rounds_done

    # -- round execution ----------------------------------------------------

    def n_steps(self, data_k) -> int:
        if self.cfg.local_steps:
            return self.cfg.local_steps
        n = jax.tree_util.tree_leaves(data_k)[0].shape[0]
        return max(1, n // self.cfg.batch_size)

    def client_steps(self, data: Sequence) -> np.ndarray:
        """int32[K] local steps per client this round — constant under
        ``cfg.local_steps``, per-client epoch length (``n_k // B``) in
        epoch mode; the source of the stacked backends' step mask."""
        return np.asarray([self.n_steps(d) for d in data], np.int32)

    def run_round(self, state, data: Sequence, t: int, key,
                  active=None) -> Tuple[Any, Dict[str, np.ndarray]]:
        """One full federated round: local steps on every ACTIVE client,
        then one graph exchange. ``data`` is a sequence of per-client data
        pytrees; ``key`` is the round key (client k steps with
        ``fold_in(key, k)``, matching the historical schedule)."""
        if active is None:
            active = active_mask(t, self.K, self.cfg)
        act = None if active is None else np.asarray(active, bool)
        if act is not None:
            assert act.shape == (self.K,)
        if self.backend == "loop":
            state, metrics = self._round_loop(state, data, t, key, act)
        elif self._stale:
            state, metrics = self._round_stale(state, data, t, key, act)
        elif self._hier:
            state, metrics = self._round_hier(state, data, t, key, act)
        else:
            state, metrics = self._round_stacked(state, data, t, key, act)
        for k, acc in enumerate(self.accountants):
            if acc is not None and (act is None or act[k]):
                acc.step(self.n_steps(data[k]))
        return state, metrics

    def run_rounds(self, state, data: Sequence, t0: int, n_rounds: int,
                   key) -> Tuple[Any, Dict[str, np.ndarray]]:
        """Engine-owned round-block: rounds ``t0 .. t0+n_rounds-1`` with the
        host re-entered only at the block edge.

        ``key`` is the run's BASE key (not a pre-folded round key): round t
        steps under ``round_key(key, t)``, folded in-scan, which is exactly
        the per-round schedule every driver historically used — so any
        block size replays the identical trajectory bit-for-bit, and a
        resume landing on a block edge continues it.

        vmap backend: the whole block is ONE compiled XLA program — an
        outer ``lax.scan`` over rounds around the per-round scan/vmap body,
        with the block's exchange matrices precomputed host-side as one
        stacked ``mix_schedule`` [T, K, K] runtime argument (one
        compilation serves every block of the same shape). shard_map: the
        per-round collective schedules are trace-time static, so the block
        is the rounds unrolled inside one jit. loop backend (and
        ``n_rounds == 1``): per-round semantics, unchanged — the
        bit-identity reference.

        Dropout (§3.4) replays the per-round ``active_mask`` schedule
        (``active_schedule``); attached accountants are bulk-stepped once
        per block (``PrivacyAccountant.step(n)`` over each client's active
        rounds), which lands on the same counters as per-round stepping.

        shard_map UNDER DROPOUT also takes the per-round path: its
        collective schedules are trace-time static, so a (typically
        unique) membership trajectory would compile a fresh T-round
        unrolled program every block, where per-round execution reuses one
        cached program per (shift, pattern).

        The async backend at staleness>0 runs :meth:`_rounds_block_stale`
        — the same outer scan with the τ-deep in-flight buffer in the
        carry (rounds interleave INSIDE the block; dropout stays on the
        blocked path since the stale splits are runtime arguments); at
        staleness=0 it runs the vmap block verbatim. The hier backend at
        n_shards>1 runs :meth:`_rounds_block_hier` — the factored
        two-level exchange in the same outer scan (the stacked factored
        schedules are runtime arguments, so dropout stays blocked too),
        with the cross-shard buffer joining the carry when staleness>0;
        at n_shards=1 it runs the vmap block verbatim.

        Returns ``(state, metrics)`` with each metric stacked to
        ``[n_rounds, K]`` (row i = round t0+i, NaN for inactive clients).
        """
        assert n_rounds >= 1, n_rounds
        if self.backend == "loop" or n_rounds == 1 or (
                self.backend == "shard_map" and self.cfg.dropout_rate):
            rows = []
            for t in range(t0, t0 + n_rounds):
                state, m = self.run_round(state, data, t, round_key(key, t))
                rows.append(m)
            return state, _stack_metric_rows(rows, self.K)
        block = (self._rounds_block_stale if self._stale else
                 self._rounds_block_hier if self._hier else
                 self._rounds_block)
        # host span: everything before the block's device work can start
        # (schedules, uploads, the enqueue), ending before the metrics'
        # device-to-host copy waits on the block
        with jax.profiler.TraceAnnotation("fl.dispatch"):
            state, ms, act_stack = block(
                state, data, t0, n_rounds, key,
                active_schedule(t0, n_rounds, self.K, self.cfg))
        return state, self._finish_block(ms, act_stack, data)

    def _finish_block(self, ms, act_stack, data):
        """Shared block epilogue: pull the stacked [T, K] metrics to host
        and bulk-step attached accountants over each client's ACTIVE
        rounds. ONE definition for the sync and stale block paths, so the
        DP step schedule cannot diverge between backends."""
        metrics = {k: np.asarray(v) for k, v in ms.items()}
        for k, acc in enumerate(self.accountants):
            if acc is not None:
                n_active_rounds = int(act_stack[:, k].sum())
                if n_active_rounds:
                    acc.step(n_active_rounds * self.n_steps(data[k]))
        return metrics

    def _rounds_block(self, state, data, t0, T, key, act_sched):
        data_s, n_valid, pass_nv, n_steps, step_masked, steps_dev = \
            self._stacked_inputs(data)
        act_stack = (np.ones((T, self.K), bool) if act_sched is None
                     else act_sched)
        mixing = self.mix != "none" and self.K > 1
        Ps = jnp.zeros((T, 1))  # placeholder when no matmul mix runs
        if self.backend != "shard_map":  # vmap, or async at staleness=0
            rkey = ("vmap_block", T, n_steps, step_masked, pass_nv)
            if rkey not in self._rounds:
                self._rounds[rkey] = self._build_block(
                    T, n_steps, self._mix_matmul_op() if mixing else None,
                    step_masked, pass_nv)
            if mixing:
                Ps = jnp.asarray(
                    mix_schedule(self.mix, t0, T, self.K, self.cfg.topology,
                                 active=act_sched), jnp.float32)
        else:
            # full-membership only here (dropout delegated to per-round):
            # the block's ppermute schedule is just the shift sequence
            topo, _ = self._mix_topology()
            shifts = (tuple(int(s) for s in
                            shift_schedule(t0, T, self.K, topo))
                      if mixing else (None,) * T)
            rkey = ("shard_block", T, n_steps, step_masked, pass_nv,
                    self.mix, shifts)
            if rkey not in self._rounds:
                mix_ops = [self._shard_mix_op(t, None) if mixing else None
                           for t in range(t0, t0 + T)]
                self._rounds[rkey] = self._build_block(
                    T, n_steps, mix_ops, step_masked, pass_nv)
        ts = jnp.arange(t0, t0 + T, dtype=jnp.int32)
        if self._compressed and mixing:
            clients, ef_state, ms = self._rounds[rkey](
                self._clients_of(state), state["ef_state"], data_s, n_valid,
                steps_dev, Ps, jnp.asarray(act_stack), ts, key)
            state = {"clients": clients, "ef_state": ef_state}
        else:
            clients, ms = self._rounds[rkey](
                self._clients_of(state), data_s, n_valid, steps_dev, Ps,
                jnp.asarray(act_stack), ts, key)
            state = ({"clients": clients, "ef_state": state["ef_state"]}
                     if self._compressed else clients)
        return state, ms, act_stack

    # -- loop backend --------------------------------------------------------

    def _one_step(self, k: int):
        """(state, data_k, chain_key) -> (state, chain_key, metrics) —
        the same composed body the vmap/shard scan uses, jitted once per
        DISTINCT step_fn (homogeneous cohorts share one compilation).
        Masked samplers get the client's true length here too (the
        unpadded leading dim — same value the stacked path passes, so the
        index draws are identical AND a sampler with a required
        ``n_valid`` parameter works on every backend)."""
        step_fn, sample = self.step_fns[k], self.sample_fn
        masked = self._masked_sampler
        cached = self._loop_steps.get(id(step_fn))
        if cached is None:
            def one(state, data_k, key):
                key, kb, kn = jax.random.split(key, 3)
                # n_valid is only well-defined when every leaf shares the
                # example axis; trees with auxiliary leaves keep the
                # sampler's own default (shape-derived) bound
                dims = {x.shape[0] for x in jax.tree_util.tree_leaves(data_k)
                        if getattr(x, "ndim", 0)}
                if masked and len(dims) == 1:
                    batch = sample(data_k, kb, n_valid=dims.pop())
                else:
                    batch = sample(data_k, kb)
                state, m = step_fn(state, batch, kn)
                return state, key, m

            cached = self._loop_steps[id(step_fn)] = jax.jit(one)
        return cached

    def _round_loop(self, state, data, t, key, act):
        ef_state = state["ef_state"] if self._compressed else None
        states = list(self._clients_of(state))  # same no-aliasing contract
        per_client: List[Optional[Dict]] = [None] * self.K
        for k in range(self.K):
            if act is not None and not act[k]:
                continue
            one = self._one_step(k)
            ck = jax.random.fold_in(key, k)
            s = states[k]
            m: Dict = {}
            for _ in range(self.n_steps(data[k])):
                s, ck, m = one(s, data[k], ck)
            states[k] = s
            per_client[k] = m
        if self.mix != "none" and self.K > 1:
            P = mix_matrix(self.mix, t, self.K, self.cfg.topology, act)
            flat = jnp.stack([tree_flatten_vector(s["proxy"]["params"])
                              for s in states])
            if self.verify_commitments or self.transmit_tamper is not None:
                flat = self._verified_exchange(flat, states, t)
            w = jnp.asarray([jnp.asarray(s["w"]) for s in states], flat.dtype)
            if self._compressed:
                # same compressed exchange — and the same codec RNG key
                # derivation — as the stacked round programs, so loop stays
                # the heterogeneous-capable reference of the compressed path
                unb, w2, ef_state = pushsum_mix_debiased(
                    flat, w, P, use_pallas=self.use_pallas,
                    compress=self.compress, ef_state=ef_state,
                    key=compress_round_key(key))
            else:
                unb, w2 = pushsum_mix_debiased(flat, w, P,
                                               use_pallas=self.use_pallas)
            like = states[0]["proxy"]["params"]
            for k in range(self.K):
                states[k] = dict(states[k])
                states[k]["proxy"] = dict(
                    states[k]["proxy"],
                    params=tree_unflatten_vector(unb[k], like))
                states[k]["w"] = w2[k]
        keys = set().union(*(m.keys() for m in per_client if m is not None))
        # heterogeneous clients may emit different metric keys — absent
        # entries collate as NaN instead of raising
        metrics = {kk: np.asarray([float(m[kk]) if m is not None and kk in m
                                   else np.nan for m in per_client])
                   for kk in sorted(keys)}
        if self._compressed:
            return {"clients": states, "ef_state": ef_state}, metrics
        return states, metrics

    def _verified_exchange(self, flat, states, t: int):
        """Commitment-checked wire hop of the loop backend's exchange.

        Each sender DECLARES the commitment of the proxy it releases
        (hashed from its parameter tree, the same digest its audit-trail
        entries carry); the stacked wire payload then passes through the
        adversary hook (``transmit_tamper``, when injected); finally every
        receiver reconstructs the per-client trees from the received rows
        and recomputes the commitments. Any row whose digest no longer
        matches its sender's declaration raises ``CommitmentError`` naming
        the client and round BEFORE the tampered mass can be mixed. This is
        an in-process simulation of the cross-host protocol (declare →
        transmit → recompute → compare); the untampered path returns the
        payload bit-identically, so verified and unverified runs share one
        trajectory. Only the loop backend verifies receipts — it is the
        heterogeneous/reference executor; compiled backends are covered by
        the restore-time chain verification."""
        from .commit import CommitmentError, client_commitment
        declared = [client_commitment(s["proxy"]["params"])[0]
                    for s in states]
        flat_np = np.asarray(flat)
        if self.transmit_tamper is not None:
            flat_np = np.asarray(self.transmit_tamper(np.array(flat_np), t))
            assert flat_np.shape == (self.K,) + np.shape(flat)[1:], (
                "transmit_tamper must preserve the [K, D] wire shape")
        if self.verify_commitments:
            like = states[0]["proxy"]["params"]
            for k in range(self.K):
                received, _ = client_commitment(
                    tree_unflatten_vector(jnp.asarray(flat_np[k]), like))
                if received != declared[k]:
                    raise CommitmentError(
                        f"received proxy of client {k} at round {t} does "
                        f"not match its declared commitment (declared "
                        f"{declared[k]!r}, recomputed {received!r}) — the "
                        "proxy was tampered with in flight; refusing to "
                        "mix it", round=t, client=k)
        return jnp.asarray(flat_np, flat.dtype)

    def _stack_data(self, data):
        """Padded-stacked device copy of ``data`` + per-client valid
        lengths (device + host) + per-client step counts, memoized in a
        small keyed LRU (alternating train/finetune datasets each keep
        their stacked copy instead of thrashing a single slot with a
        re-stack + re-transfer every round). Compatibility checks and the
        host-side derived arrays are computed once per dataset, not per
        round."""
        ck = id(data)
        cached = self._data_cache.get(ck)
        if cached is not None and cached[0] is data:
            self._data_cache.move_to_end(ck)
            return cached[1:]
        self._stack_misses += 1
        structs = {jax.tree_util.tree_structure(d) for d in data}
        shapes = {tuple(x.shape for x in jax.tree_util.tree_leaves(d))
                  for d in data}
        if len(structs) == 1 and len(shapes) == 1:
            # rectangular cohort (identical trees — auxiliary leaves with
            # their own leading dims included): plain stack, no padding.
            # n_valid is only well-defined when every leaf shares the
            # example axis; aux-leaf trees get None and the sampler keeps
            # its own shape-derived bound.
            stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *data)
            dims = {x.shape[0] for x in jax.tree_util.tree_leaves(data[0])
                    if getattr(x, "ndim", 0)}
            if len(dims) == 1:
                n0 = dims.pop()
                n_valid = jnp.full((len(data),), n0, jnp.int32)
                lengths = np.full(len(data), n0)
            else:
                n_valid, lengths = None, None
        elif pad_compatible(data):
            stacked, n_valid = pad_stack(data)
            lengths = np.asarray(n_valid)
        else:
            raise ValueError(
                "vmap/shard_map backends need identical per-client data "
                "trees or pad-compatible ones (one structure, equal dtypes "
                "and trailing dims; ragged LEADING dims are fine — they "
                "are padded and mask-sampled); use backend='loop' for "
                "genuinely incompatible trees")
        steps = self.client_steps(data)
        stacked, n_valid = self._place(stacked), self._place(n_valid)
        entry = (data, stacked, n_valid, lengths, steps)  # ref keeps id valid
        self._data_cache[ck] = entry
        self._data_cache.move_to_end(ck)
        while len(self._data_cache) > self._data_cache_max:
            self._data_cache.popitem(last=False)
        return entry[1:]

    def _mix_topology(self):
        """(graph topology, self-weight) realizing ``self.mix`` — mean is
        dense averaging ("full"), CWT's ring hop keeps nothing of self."""
        return {
            "pushsum": (self.cfg.topology, 0.5),
            "mean": ("full", 0.5),
            "ring": ("ring", 0.0),
            "none": (None, None),
        }[self.mix]

    def _local_phase(self, n_steps: int, step_masked: bool = False,
                     pass_n_valid: bool = True):
        """``(stacked, data, n_valid, steps, act, key) -> (trained, last)``
        — the local-update half of every stacked round program (``n_steps``
        = the scan length, i.e. the cohort-max step count), shared VERBATIM
        by the synchronous (vmap/shard_map) and stale (async) round cores
        so their local trajectories — RNG chains, batch draws, DP noise —
        are identical by construction; only the exchange differs.

        Raggedness is handled by two runtime arguments: ``n_valid`` bounds
        the sampler's index draw (padding is never sampled), and — only
        when ``step_masked`` (trace-time static: per-client step counts
        actually differ, i.e. epoch mode on a size-skewed cohort) — the
        ``steps`` array composes with the §3.4 ``active`` mask into a
        per-scan-iteration ``live`` mask: once client k has run its
        ``steps[k]`` local steps its state AND its RNG chain freeze, so it
        sits out the rest of the scan without perturbing either. Uniform-
        step rounds mask by ``active`` alone. The select runs per step, on
        the scan carry, so XLA updates the state in place: reverting
        inactive clients after the scan instead would keep the round's
        input state alive next to the carry, a second copy of every
        client's parameters and optimizer moments."""
        step_fn, sample, K = self.step_fns[0], self.sample_fn, self.K
        if self._masked_sampler and pass_n_valid:
            def one(state, data_k, nv_k, key):
                key, kb, kn = jax.random.split(key, 3)
                batch = sample(data_k, kb, n_valid=nv_k)
                state, m = step_fn(state, batch, kn)
                return state, key, m
        else:
            def one(state, data_k, nv_k, key):
                key, kb, kn = jax.random.split(key, 3)
                batch = sample(data_k, kb)
                state, m = step_fn(state, batch, kn)
                return state, key, m

        @jax.named_scope("fl.local")
        def local_fn(stacked, data, n_valid, steps, act, key):
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(
                jnp.arange(K, dtype=jnp.uint32))

            def body(carry, i):
                st, ks = carry
                st2, ks2, m = jax.vmap(one)(st, data, n_valid, ks)
                live = act & (i < steps) if step_masked else act
                st2 = _tree_where(live, st2, st)  # exhausted/inactive:
                ks2 = _tree_where(live, ks2, ks)  # state + RNG frozen
                return (st2, ks2), m

            (trained, _), ms = jax.lax.scan(
                body, (stacked, keys), jnp.arange(n_steps, dtype=jnp.int32))
            # each client's LAST EXECUTED step's metrics (matches the loop
            # backend); inactive clients report NaN
            idx = jnp.clip(steps - 1, 0, n_steps - 1)
            last = jax.tree_util.tree_map(
                lambda x: x[idx, jnp.arange(K)], ms)
            last = {k: jnp.where(act, v, jnp.nan) for k, v in last.items()}
            return trained, last

        return local_fn

    def _round_core(self, n_steps: int, mix_op, step_masked: bool = False,
                    pass_n_valid: bool = True):
        """One traceable program for the WHOLE synchronous round: the
        shared :meth:`_local_phase` followed by one graph exchange.
        ``mix_op(flat, w, P) -> (z2, w2)`` — the DE-BIASED mixed proxies
        plus the mixed weights — is the only backend difference: the
        stacked :func:`repro.core.gossip.pushsum_mix_debiased` exchange
        (vmap — P is a runtime arg, so every round reuses one compilation;
        plain matmuls or the Pallas-fused kernel per ``cfg.use_pallas``)
        or a ppermute collective (shard_map — the schedule is baked in, P
        is unused). ``mix_op=None`` skips the exchange.

        With compression active the round program's signature grows the
        codec state (each client's public copy): ``round_fn(stacked, ef_state, data, n_valid,
        steps, P, act, key) -> (trained, ef_state', last)`` and the mix_op
        contract becomes ``mix_op(flat, w, P, ef_state, ckey) -> (z2, w2,
        ef_state')`` (``ckey`` = the codec RNG key derived from the round
        key by ``compress_round_key`` — identical on every backend).
        Uncompressed engines keep the historical signature, so their
        compiled programs are byte-for-byte unchanged."""
        local = self._local_phase(n_steps, step_masked, pass_n_valid)
        compressed = self._compressed and mix_op is not None

        @jax.named_scope("fl.exchange")
        def exchange(trained, P, key, ef_state):
            theta = trained["proxy"]["params"]
            like = jax.tree_util.tree_map(lambda x: x[0], theta)
            flat = jax.vmap(tree_flatten_vector)(theta)            # [K, D]
            w = jnp.asarray(trained["w"], flat.dtype)
            if compressed:
                unb, w2, ef_state = mix_op(flat, w, P, ef_state,
                                        compress_round_key(key))
            else:
                unb, w2 = mix_op(flat, w, P)                       # on-device
            theta2 = jax.vmap(
                lambda v: tree_unflatten_vector(v, like))(unb)
            trained = dict(trained)
            trained["proxy"] = dict(trained["proxy"], params=theta2)
            trained["w"] = w2.astype(jnp.result_type(trained["w"]))
            return trained, ef_state

        if compressed:
            def round_fn(stacked, ef_state, data, n_valid, steps, P, act, key):
                trained, last = local(stacked, data, n_valid, steps, act,
                                      key)
                trained, ef_state = exchange(trained, P, key, ef_state)
                return trained, ef_state, last
        else:
            def round_fn(stacked, data, n_valid, steps, P, act, key):
                trained, last = local(stacked, data, n_valid, steps, act,
                                      key)
                if mix_op is not None:
                    trained, _ = exchange(trained, P, key, None)
                return self._place(
                    trained, jax.lax.with_sharding_constraint), last

        return round_fn

    def _stale_round_core(self, n_steps: int, mixing: bool,
                          step_masked: bool = False,
                          pass_n_valid: bool = True):
        """One traceable program for a STALE (async, τ>0) round: the shared
        :meth:`_local_phase`, then the delayed exchange of
        ``repro.core.gossip.stale_gossip_reference`` — re-bias θ = z·w,
        keep ``kept(t)``·θ, push ``sent(t) @ θ`` into the τ-deep in-flight
        buffer, merge the round-(t−τ) delivery rotating out of it, and
        de-bias by the identically-delayed weights. ``kept``/``sent`` are
        runtime arguments (one compilation serves every round and every
        membership pattern); the buffer rows travel with the state so the
        same core replays bit-identically per-round, blocked, or across a
        kill/resume. Inactive clients keep ``kept=1``/zero ``sent``
        columns (they hold their mass and send nothing) but still merge
        arriving mail — in-flight PushSum mass is never dropped.

        With compression active the signature grows the codec state
        exactly as in :meth:`_round_core`: ``round_fn(stacked,
        buf_t, buf_w, ef_state, ...) -> (trained, buf_t, buf_w, ef_state',
        last)`` — the public copy tracks the raw PushSum numerator
        θ = z·w (the quantity that enters the in-flight buffer), the
        de-bias weights stay uncompressed, so w-mass conservation is
        exact at any τ."""
        local = self._local_phase(n_steps, step_masked, pass_n_valid)
        compressed = self._compressed and mixing

        @jax.named_scope("fl.exchange")
        def exchange(trained, buf_t, buf_w, kept, sent, key, ef_state):
            theta_tree = trained["proxy"]["params"]
            like = jax.tree_util.tree_map(lambda x: x[0], theta_tree)
            flat = jax.vmap(tree_flatten_vector)(theta_tree)       # [K, D]
            w = jnp.asarray(trained["w"], flat.dtype)
            if compressed:
                unb, send_t, w2, send_w, ef_state = stale_mix_apply(
                    flat, w, kept, sent, buf_t[0], buf_w[0],
                    use_pallas=self.use_pallas, compress=self.compress,
                    ef_state=ef_state, key=compress_round_key(key))
            else:
                unb, send_t, w2, send_w = stale_mix_apply(
                    flat, w, kept, sent, buf_t[0], buf_w[0],
                    use_pallas=self.use_pallas)
            buf_t = jnp.concatenate([buf_t[1:], send_t[None]])
            buf_w = jnp.concatenate([buf_w[1:], send_w[None]])
            theta2 = jax.vmap(
                lambda v: tree_unflatten_vector(v, like))(unb)
            trained = dict(trained)
            trained["proxy"] = dict(trained["proxy"], params=theta2)
            trained["w"] = w2.astype(jnp.result_type(trained["w"]))
            return trained, buf_t, buf_w, ef_state

        if compressed:
            def round_fn(stacked, buf_t, buf_w, ef_state, data, n_valid,
                         steps, kept, sent, act, key):
                trained, last = local(stacked, data, n_valid, steps, act,
                                      key)
                trained, buf_t, buf_w, ef_state = exchange(
                    trained, buf_t, buf_w, kept, sent, key, ef_state)
                return trained, buf_t, buf_w, ef_state, last
        else:
            def round_fn(stacked, buf_t, buf_w, data, n_valid, steps, kept,
                         sent, act, key):
                trained, last = local(stacked, data, n_valid, steps, act,
                                      key)
                if mixing:
                    trained, buf_t, buf_w, _ = exchange(
                        trained, buf_t, buf_w, kept, sent, key, None)
                return trained, buf_t, buf_w, last

        return round_fn

    def _stale_split(self, t: int, act):
        """Runtime (kept[K], sent[K,K]) arguments of one stale round."""
        kept, sent = stale_mix_split(
            mix_matrix(self.mix, t, self.K, self.cfg.topology, act))
        return jnp.asarray(kept, jnp.float32), jnp.asarray(sent, jnp.float32)

    def _round_stale(self, state, data, t, key, act):
        data_s, n_valid, pass_nv, n_steps, step_masked, steps_dev = \
            self._stacked_inputs(data)
        act_arr = jnp.asarray(np.ones(self.K, bool) if act is None else act)
        mixing = self.mix != "none" and self.K > 1
        rkey = ("async", n_steps, step_masked, pass_nv, mixing)
        if rkey not in self._rounds:
            ndon = 4 if (self._compressed and mixing) else 3
            self._rounds[rkey] = jax.jit(
                self._stale_round_core(n_steps, mixing, step_masked,
                                       pass_nv),
                donate_argnums=tuple(range(ndon)))
        if mixing:
            kept, sent = self._stale_split(t, act)
        else:  # placeholders, never read
            kept = jnp.zeros((self.K,), jnp.float32)
            sent = jnp.zeros((self.K, self.K), jnp.float32)
        if self._compressed and mixing:
            clients, buf_t, buf_w, ef_state, last = self._rounds[rkey](
                state["clients"], state["stale_theta"], state["stale_w"],
                state["ef_state"], data_s, n_valid, steps_dev, kept, sent,
                act_arr, key)
            out = {"clients": clients, "stale_theta": buf_t,
                   "stale_w": buf_w, "ef_state": ef_state}
        else:
            clients, buf_t, buf_w, last = self._rounds[rkey](
                state["clients"], state["stale_theta"], state["stale_w"],
                data_s, n_valid, steps_dev, kept, sent, act_arr, key)
            out = {"clients": clients, "stale_theta": buf_t,
                   "stale_w": buf_w}
            if self._compressed:  # mix-less round: codec state untouched
                out["ef_state"] = state["ef_state"]
        metrics = {k: np.asarray(v) for k, v in last.items()}
        return out, metrics

    def _rounds_block_stale(self, state, data, t0, T, key, act_sched):
        """Async round-block: ONE compiled outer ``lax.scan`` over rounds
        whose carry holds the stacked client states AND the rotating
        in-flight buffer — rounds genuinely interleave inside the block
        (round t's local scan runs while its delivery, recorded τ rounds
        earlier, is already in the carry), and the host sees only the
        block edge. The per-round (kept, sent) splits arrive stacked as
        runtime arguments (``stale_mix_schedule``), keys fold in-scan, so
        any block size replays the per-round trajectory bit-exactly."""
        data_s, n_valid, pass_nv, n_steps, step_masked, steps_dev = \
            self._stacked_inputs(data)
        act_stack = (np.ones((T, self.K), bool) if act_sched is None
                     else act_sched)
        mixing = self.mix != "none" and self.K > 1
        compressed = self._compressed and mixing
        rkey = ("async_block", T, n_steps, step_masked, pass_nv, mixing)
        if rkey not in self._rounds:
            core = self._stale_round_core(n_steps, mixing, step_masked,
                                          pass_nv)

            if compressed:
                def block_fn(stacked, buf_t, buf_w, ef_state, data, n_valid,
                             steps, kepts, sents, acts, ts, base_key):
                    def body(carry, xs):
                        st, bt, bw, r = carry
                        kept, sent, a, t = xs
                        st, bt, bw, r, last = core(
                            st, bt, bw, r, data, n_valid, steps, kept,
                            sent, a, round_key(base_key, t))
                        return (st, bt, bw, r), last

                    (st, bt, bw, r), ms = jax.lax.scan(
                        body, (stacked, buf_t, buf_w, ef_state),
                        (kepts, sents, acts, ts))
                    return st, bt, bw, r, ms

                ndon = 4
            else:
                def block_fn(stacked, buf_t, buf_w, data, n_valid, steps,
                             kepts, sents, acts, ts, base_key):
                    def body(carry, xs):
                        st, bt, bw = carry
                        kept, sent, a, t = xs
                        st, bt, bw, last = core(st, bt, bw, data, n_valid,
                                                steps, kept, sent, a,
                                                round_key(base_key, t))
                        return (st, bt, bw), last

                    (st, bt, bw), ms = jax.lax.scan(
                        body, (stacked, buf_t, buf_w),
                        (kepts, sents, acts, ts))
                    return st, bt, bw, ms

                ndon = 3
            self._rounds[rkey] = jax.jit(
                block_fn,
                donate_argnums=tuple(range(ndon)))
        if mixing:
            kepts, sents = stale_mix_schedule(
                self.mix, t0, T, self.K, self.cfg.topology,
                active=act_sched)
            kepts = jnp.asarray(kepts, jnp.float32)
            sents = jnp.asarray(sents, jnp.float32)
        else:
            kepts = jnp.zeros((T, self.K), jnp.float32)
            sents = jnp.zeros((T, self.K, self.K), jnp.float32)
        ts = jnp.arange(t0, t0 + T, dtype=jnp.int32)
        if compressed:
            clients, buf_t, buf_w, ef_state, ms = self._rounds[rkey](
                state["clients"], state["stale_theta"], state["stale_w"],
                state["ef_state"], data_s, n_valid, steps_dev, kepts, sents,
                jnp.asarray(act_stack), ts, key)
            out = {"clients": clients, "stale_theta": buf_t,
                   "stale_w": buf_w, "ef_state": ef_state}
        else:
            clients, buf_t, buf_w, ms = self._rounds[rkey](
                state["clients"], state["stale_theta"], state["stale_w"],
                data_s, n_valid, steps_dev, kepts, sents,
                jnp.asarray(act_stack), ts, key)
            out = {"clients": clients, "stale_theta": buf_t,
                   "stale_w": buf_w}
            if self._compressed:  # mix-less block: codec state untouched
                out["ef_state"] = state["ef_state"]
        return out, ms, act_stack

    # -- hier backend (two-level factored exchange) --------------------------

    def _hier_round_core(self, n_steps: int, mixing: bool,
                         step_masked: bool = False,
                         pass_n_valid: bool = True):
        """One traceable program for a HIER round: the shared
        :meth:`_local_phase` VERBATIM (local trajectories — RNG chains,
        batch draws, DP noise — bit-identical to vmap by construction),
        then the factored two-level exchange. The factored schedule
        ``(blocks[S, L, L], src[K], scale[K])`` arrives as runtime
        arguments (one compilation serves every round and membership
        pattern). At τ=0 the exchange is
        :func:`repro.core.gossip.hier_mix_debiased` — synchronous, and
        bit-identical to the dense vmap exchange on the same P; at τ>0 it
        is :func:`repro.core.gossip.hier_stale_mix_apply` with the
        cross-shard buffer rows in the signature, rotated here exactly
        like the async buffer. Client states keep the flat [K, ...]
        layout throughout — the shard reshape is internal to the
        exchange — which is what keeps checkpoints backend-portable and
        the data stacking layout-independent."""
        local = self._local_phase(n_steps, step_masked, pass_n_valid)
        up, tau = self.use_pallas, self.staleness

        @jax.named_scope("fl.exchange")
        def exchange(trained, blocks, src, scale, buf_t, buf_w):
            theta_tree = trained["proxy"]["params"]
            like = jax.tree_util.tree_map(lambda x: x[0], theta_tree)
            flat = jax.vmap(tree_flatten_vector)(theta_tree)       # [K, D]
            w = jnp.asarray(trained["w"], flat.dtype)
            if tau:
                unb, send_t, w2, send_w = hier_stale_mix_apply(
                    flat, w, blocks, src, scale, buf_t[0], buf_w[0],
                    use_pallas=up)
                buf_t = jnp.concatenate([buf_t[1:], send_t[None]])
                buf_w = jnp.concatenate([buf_w[1:], send_w[None]])
            else:
                unb, w2 = hier_mix_debiased(flat, w, blocks, src, scale,
                                            use_pallas=up)
            theta2 = jax.vmap(
                lambda v: tree_unflatten_vector(v, like))(unb)
            trained = dict(trained)
            trained["proxy"] = dict(trained["proxy"], params=theta2)
            trained["w"] = w2.astype(jnp.result_type(trained["w"]))
            return trained, buf_t, buf_w

        if tau:
            def round_fn(stacked, buf_t, buf_w, data, n_valid, steps,
                         blocks, src, scale, act, key):
                trained, last = local(stacked, data, n_valid, steps, act,
                                      key)
                if mixing:
                    trained, buf_t, buf_w = exchange(
                        trained, blocks, src, scale, buf_t, buf_w)
                return trained, buf_t, buf_w, last
        else:
            def round_fn(stacked, data, n_valid, steps, blocks, src, scale,
                         act, key):
                trained, last = local(stacked, data, n_valid, steps, act,
                                      key)
                if mixing:
                    trained, _, _ = exchange(trained, blocks, src, scale,
                                             None, None)
                return trained, last

        return round_fn

    def _hier_split(self, t: int, act):
        """Runtime (blocks, src, scale) device arguments of one hier
        round's factored exchange."""
        blocks, src, scale = hier_mix_split(
            mix_matrix(self.mix, t, self.K, self.cfg.topology, act),
            self.n_shards)
        return (jnp.asarray(blocks, jnp.float32),
                jnp.asarray(src, jnp.int32),
                jnp.asarray(scale, jnp.float32))

    def _hier_placeholders(self, T: int = 0):
        """Never-read factored-schedule placeholders for mix-less rounds."""
        S = self.n_shards
        L = self.K // S
        lead = () if T == 0 else (T,)
        return (jnp.zeros(lead + (S, L, L), jnp.float32),
                jnp.zeros(lead + (self.K,), jnp.int32),
                jnp.zeros(lead + (self.K,), jnp.float32))

    def _round_hier(self, state, data, t, key, act):
        data_s, n_valid, pass_nv, n_steps, step_masked, steps_dev = \
            self._stacked_inputs(data)
        act_arr = jnp.asarray(np.ones(self.K, bool) if act is None else act)
        mixing = self.mix != "none" and self.K > 1
        tau = self.staleness
        rkey = ("hier", n_steps, step_masked, pass_nv, mixing)
        if rkey not in self._rounds:
            donate = tuple(range(3)) if tau else (0,)
            self._rounds[rkey] = jax.jit(
                self._hier_round_core(n_steps, mixing, step_masked,
                                      pass_nv),
                donate_argnums=donate)
        blocks, src, scale = (self._hier_split(t, act) if mixing
                              else self._hier_placeholders())
        if tau:
            clients, buf_t, buf_w, last = self._rounds[rkey](
                state["clients"], state["hier_buffer"], state["hier_w"],
                data_s, n_valid, steps_dev, blocks, src, scale, act_arr,
                key)
            out: Any = {"clients": clients, "hier_buffer": buf_t,
                        "hier_w": buf_w}
        else:
            out, last = self._rounds[rkey](
                self._clients_of(state), data_s, n_valid, steps_dev,
                blocks, src, scale, act_arr, key)
        metrics = {k: np.asarray(v) for k, v in last.items()}
        return out, metrics

    def _rounds_block_hier(self, state, data, t0, T, key, act_sched):
        """Hier round-block: ONE compiled outer ``lax.scan`` over rounds,
        consuming the block's stacked factored schedules
        (``hier_mix_schedule``: blocks[T, S, L, L] + src/scale[T, K]) as
        runtime arguments; at τ>0 the cross-shard in-flight buffer joins
        the scan carry exactly like the async buffer, so rounds
        interleave inside the block and the host sees only the edge.
        Keys fold in-scan — any block size replays the per-round
        trajectory bit-exactly."""
        data_s, n_valid, pass_nv, n_steps, step_masked, steps_dev = \
            self._stacked_inputs(data)
        act_stack = (np.ones((T, self.K), bool) if act_sched is None
                     else act_sched)
        mixing = self.mix != "none" and self.K > 1
        tau = self.staleness
        rkey = ("hier_block", T, n_steps, step_masked, pass_nv, mixing)
        if rkey not in self._rounds:
            core = self._hier_round_core(n_steps, mixing, step_masked,
                                         pass_nv)

            if tau:
                def block_fn(stacked, buf_t, buf_w, data, n_valid, steps,
                             blockss, srcs, scales, acts, ts, base_key):
                    def body(carry, xs):
                        st, bt, bw = carry
                        bl, sr, sc, a, t = xs
                        st, bt, bw, last = core(
                            st, bt, bw, data, n_valid, steps, bl, sr, sc,
                            a, round_key(base_key, t))
                        return (st, bt, bw), last

                    (st, bt, bw), ms = jax.lax.scan(
                        body, (stacked, buf_t, buf_w),
                        (blockss, srcs, scales, acts, ts))
                    return st, bt, bw, ms

                donate = tuple(range(3))
            else:
                def block_fn(stacked, data, n_valid, steps, blockss, srcs,
                             scales, acts, ts, base_key):
                    def body(st, xs):
                        bl, sr, sc, a, t = xs
                        st2, last = core(st, data, n_valid, steps, bl, sr,
                                         sc, a, round_key(base_key, t))
                        return st2, last

                    return jax.lax.scan(
                        body, stacked, (blockss, srcs, scales, acts, ts))

                donate = (0,)
            self._rounds[rkey] = jax.jit(block_fn, donate_argnums=donate)
        if mixing:
            blockss, srcs, scales = hier_mix_schedule(
                self.mix, t0, T, self.K, self.n_shards, self.cfg.topology,
                active=act_sched)
            blockss = jnp.asarray(blockss, jnp.float32)
            srcs = jnp.asarray(srcs, jnp.int32)
            scales = jnp.asarray(scales, jnp.float32)
        else:
            blockss, srcs, scales = self._hier_placeholders(T)
        ts = jnp.arange(t0, t0 + T, dtype=jnp.int32)
        if tau:
            clients, buf_t, buf_w, ms = self._rounds[rkey](
                state["clients"], state["hier_buffer"], state["hier_w"],
                data_s, n_valid, steps_dev, blockss, srcs, scales,
                jnp.asarray(act_stack), ts, key)
            out: Any = {"clients": clients, "hier_buffer": buf_t,
                        "hier_w": buf_w}
        else:
            out, ms = self._rounds[rkey](
                self._clients_of(state), data_s, n_valid, steps_dev,
                blockss, srcs, scales, jnp.asarray(act_stack), ts, key)
        return out, ms, act_stack

    def _build_round(self, n_steps: int, mix_op, step_masked: bool = False,
                     pass_n_valid: bool = True):
        """Jitted single-round program (the ``run_round`` fast path).
        The state is donated, on every platform, so XLA updates it in
        place: a caller that reads a state after handing it to a round
        fails the same way on the CPU as on the chip."""
        donate = (0,)
        if self._compressed and mix_op is not None:
            donate = (0, 1)  # stacked state AND the codec state in place
        return jax.jit(self._round_core(n_steps, mix_op, step_masked,
                                        pass_n_valid),
                       donate_argnums=donate)

    def _build_block(self, n_rounds: int, n_steps: int, mix_ops,
                     step_masked: bool = False, pass_n_valid: bool = True):
        """One jitted program for a WHOLE round-block (``n_rounds`` federated
        rounds, host re-entered only at the block edge).

        ``mix_ops`` is either ONE mix_op shared by every round — the vmap
        matmul path, where the per-round exchange matrix arrives as the
        runtime-stacked ``Ps[T, K, K]`` and the block is a ``lax.scan`` over
        rounds (one compilation serves every block of this shape) — or a
        length-``n_rounds`` sequence of per-round static ops (shard_map,
        whose ppermute schedules are trace-time static: the block is a
        Python-unrolled sequence of round bodies inside one jit, exactly
        the per-round collective schedules fused end to end).

        Per-round RNG keys are folded IN-SCAN from the base key
        (``round_key(base_key, t)`` with the runtime ``ts`` round indices),
        so a blocked run replays the per-round key schedule bit-exactly.
        Donation as in :meth:`_build_round`."""
        donate = (0,)
        if not isinstance(mix_ops, (list, tuple)):
            core = self._round_core(n_steps, mix_ops, step_masked,
                                    pass_n_valid)

            if self._compressed and mix_ops is not None:
                # compressed vmap block: the codec state joins the scan
                # carry exactly like the async τ-buffer, so any block size
                # replays the per-round public-copy trajectory bit-exactly
                def block_fn(stacked, ef_state, data, n_valid, steps, Ps,
                             acts, ts, base_key):
                    def body(carry, xs):
                        st, r = carry
                        P, act, t = xs
                        st2, r2, last = core(st, r, data, n_valid, steps,
                                             P, act, round_key(base_key, t))
                        return (st2, r2), last

                    (st, r), ms = jax.lax.scan(
                        body, (stacked, ef_state), (Ps, acts, ts))
                    return st, r, ms

                donate = (0, 1)
            else:
                def block_fn(stacked, data, n_valid, steps, Ps, acts, ts,
                             base_key):
                    def body(st, xs):
                        P, act, t = xs
                        st2, last = core(st, data, n_valid, steps, P, act,
                                         round_key(base_key, t))
                        return st2, last

                    return jax.lax.scan(body, stacked, (Ps, acts, ts))
        else:
            cores = [self._round_core(n_steps, op, step_masked, pass_n_valid)
                     for op in mix_ops]

            def block_fn(stacked, data, n_valid, steps, Ps, acts, ts,
                         base_key):
                lasts = []
                for i, core in enumerate(cores):
                    stacked, last = core(stacked, data, n_valid, steps,
                                         Ps[i], acts[i],
                                         round_key(base_key, ts[i]))
                    lasts.append(last)
                stacked_ms = jax.tree_util.tree_map(
                    lambda *xs: jnp.stack(xs), *lasts)
                return stacked, stacked_ms

        return jax.jit(block_fn, donate_argnums=donate)

    def _mix_matmul_op(self):
        """The stacked matmul exchange as a mix_op: ``(flat, w, P) ->
        (z2, w2)`` de-biased, dispatched plain-XLA or Pallas-fused per
        ``cfg.use_pallas``; with compression active the contract grows the
        codec-state/key operands (``(flat, w, P, ef_state, ckey) -> (z2,
        w2, ef_state2)``) and the exchange takes the compressed plain-XLA path
        (``use_pallas`` is a no-op there — the fused kernel is
        uncompressed-only). One definition serves the single-round and
        round-block programs so the two paths cannot drift."""
        up = self.use_pallas
        if self._compressed:
            spec = self.compress
            return lambda flat, w, P, ef_state, ckey: pushsum_mix_debiased(
                flat, w, P, use_pallas=up, compress=spec, ef_state=ef_state,
                key=ckey)
        return lambda flat, w, P: pushsum_mix_debiased(flat, w, P,
                                                       use_pallas=up)

    def _shard_mix_op(self, t: int, act_key):
        """ppermute exchange along ``self.axis``; t/active are trace-time
        static (new collective schedule per membership pattern). The
        collective returns pre-debias (mixed, w2); the de-bias divide
        happens here so the mix_op contract matches the matmul path."""
        topo, sw = self._mix_topology()
        spec = jax.sharding.PartitionSpec(self.axis)
        gossip_sm = jax.shard_map(
            lambda f, w: pushsum_gossip_shard(
                f, w, t, self.axis, self.K, topo, sw, active=act_key),
            mesh=self.mesh, in_specs=(spec, spec), out_specs=(spec, spec),
            check_vma=False)

        def op(flat, w, P):
            mixed, w2 = gossip_sm(flat, w)
            return mixed / w2[:, None], w2

        return op

    def _stacked_inputs(self, data):
        """Shared prologue of the stacked round/block programs: padded
        device copy, masked-sampler validation, scan length and step-mask
        staticness derived from the per-client step counts."""
        data_s, n_valid, lengths, steps_arr = self._stack_data(data)
        if lengths is not None and (lengths != lengths[0]).any() \
                and not self._masked_sampler:
            raise ValueError(
                "ragged per-client datasets on the stacked path need a "
                "masked sampler: sample_fn must accept (data_k, key, "
                "n_valid) so padding is never drawn (see "
                "repro.core.engine.classifier_sampler)")
        pass_nv = n_valid is not None
        if n_valid is None:  # aux-leaf rectangular tree: dummy, never read
            n_valid = jnp.zeros((self.K,), jnp.int32)
        n_steps = int(steps_arr.max())
        # trace-time static: per-step state/RNG masking is only needed when
        # clients genuinely run different step counts (epoch mode on a
        # size-skewed cohort); uniform rounds keep the mask-free body
        step_masked = bool((steps_arr != steps_arr[0]).any())
        return data_s, n_valid, pass_nv, n_steps, step_masked, \
            jnp.asarray(steps_arr)

    def _round_stacked(self, state, data, t, key, act):
        data_s, n_valid, pass_nv, n_steps, step_masked, steps_dev = \
            self._stacked_inputs(data)
        stacked = self._clients_of(state)
        act_arr = jnp.asarray(np.ones(self.K, bool) if act is None else act)
        mixing = self.mix != "none" and self.K > 1
        P = jnp.zeros((0,))  # placeholder when no matmul mix runs
        if self.backend != "shard_map":  # vmap, or async at staleness=0
            rkey = ("vmap", n_steps, step_masked, pass_nv)
            if rkey not in self._rounds:
                self._rounds[rkey] = self._build_round(
                    n_steps, self._mix_matmul_op() if mixing else None,
                    step_masked, pass_nv)
            if mixing:
                P = jnp.asarray(
                    mix_matrix(self.mix, t, self.K, self.cfg.topology, act),
                    jnp.float32)
        else:
            A = self.K if act is None else int(act.sum())
            topo, _ = self._mix_topology()
            # cache key: the ppermute schedule is fully determined by the
            # (mix-mapped) shift and the membership pattern
            shift = gossip_shift(t, A, topo) if mixing else None
            act_key = None if act is None else tuple(bool(a) for a in act)
            rkey = ("shard", n_steps, shift, act_key, self.mix, step_masked,
                    pass_nv)
            if rkey not in self._rounds:
                self._rounds[rkey] = self._build_round(
                    n_steps,
                    self._shard_mix_op(t, act_key) if mixing else None,
                    step_masked, pass_nv)
        if self._compressed and mixing:
            stacked, ef_state, last = self._rounds[rkey](
                stacked, state["ef_state"], data_s, n_valid, steps_dev, P,
                act_arr, key)
            out: Any = {"clients": stacked, "ef_state": ef_state}
        else:
            stacked, last = self._rounds[rkey](
                stacked, data_s, n_valid, steps_dev, P, act_arr, key)
            out = ({"clients": stacked, "ef_state": state["ef_state"]}
                   if self._compressed else stacked)
        metrics = {k: np.asarray(v) for k, v in last.items()}
        return out, metrics


# ---------------------------------------------------------------------------
# factories: classifier-scale engines built from ModelSpecs


def classifier_sampler(batch_size: int) -> SampleFn:
    """Uniform-with-replacement batch draw from (x, y) — the historical
    client sampling used by ``local_round``/``_ce_local_round``.

    Masked: on the stacked (padded) path the engine passes the client's
    true length ``n_valid`` and indices are drawn ``randint(0, n_valid)``,
    so padding rows are never sampled. Without it (loop backend, where the
    data is unpadded) the bound is ``x.shape[0]`` — the same value, so
    loop and vmap draw identical batches on ragged cohorts."""

    def sample(data_k, kb, n_valid=None):
        x, y = data_k
        hi = x.shape[0] if n_valid is None else n_valid
        idx = jax.random.randint(kb, (batch_size,), 0, hi)
        return (x[idx], y[idx])

    return sample


def _dml_state_step(private_spec, proxy_spec, cfg: ProxyFLConfig) -> StepFn:
    from .protocol import dml_step_fn
    raw = dml_step_fn(private_spec, proxy_spec, cfg)

    def step(state, batch, key):
        phi, opt_phi, theta, opt_theta, m = raw(
            state["private"]["params"], state["private"]["opt"],
            state["proxy"]["params"], state["proxy"]["opt"], batch, key)
        return {"private": {"params": phi, "opt": opt_phi},
                "proxy": {"params": theta, "opt": opt_theta},
                "w": state["w"]}, m

    return step


def _dml_state_init(private_spec, proxy_spec, cfg: ProxyFLConfig) -> InitFn:
    opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)

    def init(key):
        kf, kh = jax.random.split(key)
        phi = private_spec.init(kf)
        theta = proxy_spec.init(kh)
        return {"private": {"params": phi, "opt": opt.init(phi)},
                "proxy": {"params": theta, "opt": opt.init(theta)},
                "w": jnp.ones((), jnp.float32)}

    return init


def _ce_state_step(spec, cfg: ProxyFLConfig, dp: bool) -> StepFn:
    from .protocol import ce_step_fn
    raw = ce_step_fn(spec, cfg, dp)

    def step(state, batch, key):
        params, opt, loss = raw(state["proxy"]["params"],
                                state["proxy"]["opt"], batch, key)
        return {"proxy": {"params": params, "opt": opt},
                "w": state["w"]}, {"loss": loss}

    return step


def _ce_state_init(spec, cfg: ProxyFLConfig) -> InitFn:
    opt = Adam(lr=cfg.lr, weight_decay=cfg.weight_decay)

    def init(key):
        params = spec.init(key)
        return {"proxy": {"params": params, "opt": opt.init(params)},
                "w": jnp.ones((), jnp.float32)}

    return init


@functools.lru_cache(maxsize=8)
def dml_engine(private_specs: Tuple, proxy_spec, cfg: ProxyFLConfig,
               backend: str = "auto", mix: str = "pushsum"
               ) -> FederationEngine:
    """Engine for the two-model (private+proxy DML) family: ProxyFL
    (mix="pushsum") and FML (mix="mean"). ``backend="auto"`` picks vmap
    for homogeneous cohorts — including ragged (size-skewed) datasets,
    which the stacked path pads and mask-samples — and loop only for
    heterogeneous private architectures. A small LRU lets repeated
    federations with the same specs reuse compiled round programs without
    pinning every sweep configuration's engine (and its device-resident
    stacked data) in memory forever."""
    K = len(private_specs)
    homogeneous = all(s == private_specs[0] for s in private_specs)
    if backend == "auto":
        backend = "vmap" if homogeneous else "loop"
    if homogeneous:
        step_fns: Any = _dml_state_step(private_specs[0], proxy_spec, cfg)
        init_fns: Any = _dml_state_init(private_specs[0], proxy_spec, cfg)
    else:
        step_fns = [_dml_state_step(s, proxy_spec, cfg) for s in private_specs]
        init_fns = [_dml_state_init(s, proxy_spec, cfg) for s in private_specs]
    return FederationEngine(
        cfg, n_clients=K, step_fns=step_fns, init_fns=init_fns,
        sample_fn=classifier_sampler(cfg.batch_size), backend=backend, mix=mix)


@functools.lru_cache(maxsize=8)
def single_model_engine(spec, cfg: ProxyFLConfig, dp: bool,
                        mix: str = "mean", backend: str = "auto",
                        n_clients: int = 0) -> FederationEngine:
    """Engine for the single-model baselines: FedAvg (mix="mean"), AvgPush
    ("pushsum"), CWT ("ring"), Regular/Joint ("none"). The model lives in
    the gossiped ``proxy`` slot of the engine state."""
    K = n_clients or cfg.n_clients
    return FederationEngine(
        cfg, n_clients=K,
        step_fns=_ce_state_step(spec, cfg, dp),
        init_fns=_ce_state_init(spec, cfg),
        sample_fn=classifier_sampler(cfg.batch_size),
        backend="vmap" if backend == "auto" else backend, mix=mix)
