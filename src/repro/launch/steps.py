"""LLM-scale ProxyFL steps — the paper's Algorithm 1 applied to the assigned
architectures on the production mesh.

Three program kinds are built here and lowered by ``dryrun.py``:

* ``train_step``    — ONE client's local DML step (Algorithm 1 lines 2–5):
                      private model updated on Eq. (4) without DP, proxy
                      updated on Eq. (5) with per-example DP-SGD (Eq. 7).
* ``fl_round_step`` — a FULL ProxyFL round with one federated client per
                      pod: vmapped DML over the stacked client dim followed
                      by the PushSum proxy exchange, realized as a single
                      ``jax.lax.ppermute`` along the "pod" mesh axis
                      (Algorithm 1 lines 7–11).
* ``hier_round_block_step`` — the TWO-LEVEL round-block: one shard of
                      stacked clients per pod; the flat PushSum matrix is
                      factored into a local intra-shard matmul plus at most
                      two cross-shard ``ppermute``s per round (the engine's
                      ``backend="hier"`` at production-mesh scale).
* ``prefill_step`` / ``decode_step`` — inference on the client's private
                      model (the paper: "After training, a client's private
                      model can be used for inference").

Everything here is shape-polymorphic over the assigned architectures and is
exercised at full scale only through ``.lower().compile()`` with
``jax.ShapeDtypeStruct`` stand-ins (no allocation).
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import InputShape, ModelConfig, ProxyFLConfig
from ..core.dp import dp_gradient_chunked, non_dp_gradient
from ..core.gossip import gossip_shift, hier_mix_schedule
from ..nn.losses import dml_loss
from ..nn.model import forward, init_cache, init_model
from ..nn.modules import tree_flatten_vector, tree_unflatten_vector
from ..optim import Adam
from .sharding import batch_pspecs, cache_pspecs

Params = Any


@dataclass(frozen=True)
class StepOptions:
    """Implementation knobs (the §Perf hillclimb levers)."""

    remat: bool = True            # activation-checkpoint the layer-stack scan
    accum: int = 8                # private-grad microbatch accumulation chunks
    dp_chunk: int = 8             # examples per DP vmap chunk (scan over chunks)
    moment_dtype: str = "float32"  # Adam m/v dtype ("bfloat16" halves opt HBM)
    kv_chunk: int = 1024          # online-softmax KV chunk length
    mamba_chunk: int = 256        # Mamba chunked-scan block length
    expert_parallel: bool = False  # shard experts (not d_ff) over "model"
    logits_dtype: str = "float32"  # loss-side logits precision
    serve_2d: bool = False         # weight-stationary 2D-TP decode: params
    # sharded over (data × model), decode batch REPLICATED over data, KV
    # cache sequence-sharded — the per-step ZeRO-3 weight gathers become
    # small activation psums instead (§Perf hillclimb B, qwen1.5-110b)
    shard_acts: bool = False       # with_sharding_constraint on activations
    # (set by dryrun/train on a mesh; default False so single-device tests
    # and the paper-scale simulation backend never reference mesh axes)

    def with_(self, **kw) -> "StepOptions":
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins — never allocated)


def input_specs(cfg: ModelConfig, shape: InputShape, *, n_clients: int = 0
                ) -> Dict[str, jax.ShapeDtypeStruct]:
    """Model inputs for one step at ``shape``. With ``n_clients`` > 0 a
    leading stacked-client dim is added (the multi-pod FL-round layout)."""
    B, S = shape.global_batch, shape.seq_len
    lead = (n_clients,) if n_clients else ()

    def tok(shape_):
        return jax.ShapeDtypeStruct(lead + shape_, jnp.int32)

    if shape.kind == "train":
        if cfg.modality == "audio":
            specs = {"tokens": tok((B, S, cfg.n_codebooks)),
                     "labels": tok((B, S, cfg.n_codebooks))}
        else:
            specs = {"tokens": tok((B, S)), "labels": tok((B, S))}
        if cfg.modality == "vlm":
            specs["img"] = jax.ShapeDtypeStruct(
                lead + (B, cfg.n_image_tokens, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        return specs
    if shape.kind == "prefill":
        specs = {"tokens": tok((B, S, cfg.n_codebooks)) if cfg.modality == "audio"
                 else tok((B, S))}
        if cfg.modality == "vlm":
            specs["img"] = jax.ShapeDtypeStruct(
                lead + (B, cfg.n_image_tokens, cfg.frontend_dim), jnp.dtype(cfg.dtype))
        return specs
    if shape.kind == "decode":
        return {"tokens": tok((B, 1, cfg.n_codebooks)) if cfg.modality == "audio"
                else tok((B, 1)),
                "pos": jax.ShapeDtypeStruct(lead, jnp.int32)}
    raise ValueError(shape.kind)


# ---------------------------------------------------------------------------
# state construction (init fns; shapes via jax.eval_shape in dryrun)


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


def _split_batch(cfg: ModelConfig, batch: Dict):
    return batch["tokens"], batch["labels"], batch.get("img")


def _text_logits(cfg: ModelConfig, logits: jnp.ndarray) -> jnp.ndarray:
    """Drop image-position logits so labels align with text tokens."""
    if cfg.modality == "vlm" and cfg.n_image_tokens:
        return logits[:, cfg.n_image_tokens:]
    return logits


def _constrain_batch(batch: Dict, opts: StepOptions) -> Dict:
    """Pin the batch dim of every batch leaf to the "data" mesh axis.

    Without this, GSPMD propagation through the loss region can decide to
    replicate the (micro)batch and shard vocab instead — turning the CE
    backward into multi-GiB cross-data all-reduces (observed on
    qwen1.5-4b × train_4k before this constraint existed)."""
    if not opts.shard_acts:
        return batch
    return {k: jax.lax.with_sharding_constraint(
                v, P(*(("data",) + (None,) * (v.ndim - 1))))
            for k, v in batch.items() if v is not None}


def _constrain_logits(logits, opts: StepOptions):
    """Logits [B, S, ..., V]: batch on "data", vocab on "model". Inside the
    per-example DP vmap (leading dim 1, example dim carried by
    ``spmd_axis_name="data"``) the batch axis must stay unconstrained."""
    if not opts.shard_acts:
        return logits
    b = "data" if logits.shape[0] > 1 else None
    spec = (b,) + (None,) * (logits.ndim - 2) + ("model",)
    return jax.lax.with_sharding_constraint(logits, P(*spec))


def _forward_logits(params, cfg: ModelConfig, tokens, img, opts: StepOptions):
    ea = "model" if (opts.shard_acts and opts.expert_parallel) else None
    # batch pin only when the (micro)batch can actually divide the data axis
    # (the per-example DP vmap carries its batch via spmd_axis_name instead)
    ba = "data" if (opts.shard_acts and tokens.shape[0] > 1) else None
    # pin the residual stream [B, S, d] between layers: without it the
    # GSPMD solver shards the scan carry on d(model) with batch REPLICATED,
    # and every saved activation / backward dgrad runs at full batch
    # (observed on deepseek-v2 × train_4k: f32[59, 32, 4096, 320] residual
    # stacks and TB-scale dot_general all-reduces)
    act = ("data", None, None) if ba else None
    logits, _, aux = forward(params, cfg, tokens, img, remat=opts.remat,
                             kv_chunk=opts.kv_chunk, mamba_chunk=opts.mamba_chunk,
                             moe_expert_axis=ea, batch_axis=ba, act_spec=act)
    return _constrain_logits(_text_logits(cfg, logits), opts), aux


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
            mb = _constrain_batch(mb, opts)
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
                    constrain=lambda b: _constrain_batch(b, opts),
                    prepare_chunk=prepare,
                    spmd_axis_name="data" if opts.shard_acts else None)
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
# FL round step (multi-pod — one client per pod, gossip on the pod axis)


def make_fl_round_step(cfg_priv: ModelConfig, cfg_proxy: ModelConfig,
                       fl: ProxyFLConfig, mesh, n_clients: int,
                       opts: StepOptions = StepOptions(),
                       round_t: int = 0):
    """Full Algorithm-1 round: vmapped local DML over the stacked client dim
    (sharded on "pod"), then the PushSum exchange as ONE collective-permute
    along "pod" — the TPU-native realization of the paper's O(1)-per-round
    communication claim. ``round_t`` is static (the graph P^(t) is known at
    trace time, exactly like the paper's per-round permutation)."""
    dml = make_train_step(cfg_priv, cfg_proxy, fl, opts)
    shift = gossip_shift(round_t, n_clients, fl.topology)
    self_w = 0.5

    def gossip(flat, w):
        # flat: [K_local(=1 per pod), D]; w: [K_local]
        if shift == 0:
            return flat, w
        perm = [(i, (i + shift) % n_clients) for i in range(n_clients)]
        send_f = (1.0 - self_w) * flat
        send_w = (1.0 - self_w) * w
        recv_f = jax.lax.ppermute(send_f, "pod", perm)
        recv_w = jax.lax.ppermute(send_w, "pod", perm)
        return self_w * flat + recv_f, self_w * w + recv_w

    gossip_sm = jax.shard_map(
        gossip, mesh=mesh,
        in_specs=(P("pod"), P("pod")),
        out_specs=(P("pod"), P("pod")), check_vma=False)

    def round_step(stacked_state, stacked_batch, keys):
        # local DML on every client in parallel (clients stacked on "pod")
        new_state, metrics = jax.vmap(dml)(stacked_state, stacked_batch, keys)
        # PushSum exchange of the proxies (Algorithm 1 lines 7–11)
        theta = new_state["proxy"]["params"]
        flat = jax.vmap(tree_flatten_vector)(theta)           # [K, D]
        w = new_state["w"]                                    # [K]
        mixed, w2 = gossip_sm(flat, w)
        unb = mixed / jnp.maximum(w2, 1e-9)[:, None]          # de-bias θ/w
        theta2 = jax.vmap(lambda v: tree_unflatten_vector(v, jax.tree_util.tree_map(
            lambda x: x[0], theta)))(unb)
        new_state = dict(new_state)
        new_state["proxy"] = dict(new_state["proxy"], params=theta2)
        new_state["w"] = w2
        return new_state, metrics

    return round_step


def make_round_block_step(cfg_priv: ModelConfig, cfg_proxy: ModelConfig,
                          fl: ProxyFLConfig, mesh, n_clients: int,
                          opts: StepOptions = StepOptions(),
                          n_rounds: int = 4, t0: int = 0):
    """A whole FUSED round-block as one program: ``n_rounds`` consecutive
    Algorithm-1 rounds (local DML + PushSum ppermute each) unrolled inside
    a single jit — the multi-pod counterpart of the FederationEngine's
    round-blocks, and the unit ``dryrun.py --program round_block`` lowers
    so the roofline reports amortized per-BLOCK cost (the per-round
    collective schedules are static, exactly like ``_build_block``'s
    shard_map path). Per-round keys fold in from the stacked client keys,
    so the block replays the same per-round RNG schedule as ``n_rounds``
    separate ``make_fl_round_step`` dispatches with ``fold_in(keys, t)``
    applied by the host. Metrics come back stacked [n_rounds, K]."""
    rounds = [make_fl_round_step(cfg_priv, cfg_proxy, fl, mesh, n_clients,
                                 opts, round_t=t0 + i)
              for i in range(n_rounds)]

    def block_step(stacked_state, stacked_batch, keys):
        ms = []
        for i, round_step in enumerate(rounds):
            round_keys = jax.vmap(
                lambda kk: jax.random.fold_in(kk, t0 + i))(keys)
            stacked_state, m = round_step(stacked_state, stacked_batch,
                                          round_keys)
            ms.append(m)
        metrics = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ms)
        return stacked_state, metrics

    return block_step


def make_hier_round_block_step(cfg_priv: ModelConfig, cfg_proxy: ModelConfig,
                               fl: ProxyFLConfig, mesh, n_shards: int,
                               clients_per_shard: int,
                               opts: StepOptions = StepOptions(),
                               n_rounds: int = 4, t0: int = 0):
    """Two-level (hier) fused round-block: one SHARD of ``clients_per_shard``
    clients per pod, ``n_shards`` = pod count. Each round the flat PushSum
    matrix P^(t) is factored by edge locality (``hier_mix_schedule``): the
    block-diagonal intra-shard part runs as a LOCAL [L, L] matmul over each
    pod's stacked clients (no wire traffic), and the at-most-one cross-shard
    edge per client is realized as a distributed roll — the uniform shift
    σ(t) decomposed as ``q·L + r`` needs at most two ``ppermute``s of the
    [L, D] shard block along "pod" (rounds with σ(t) < L that stay inside
    the shard boundary need at most one). Per-client wire bytes stay O(D),
    independent of K — the paper's O(1)-per-round communication claim at
    the two-level scale ``dryrun.py --program hier_block`` lowers."""
    dml = make_train_step(cfg_priv, cfg_proxy, fl, opts)
    S, L = n_shards, clients_per_shard
    K = S * L

    def make_exchange(t):
        shift = gossip_shift(t, K, fl.topology) % K
        if shift == 0:
            return None
        blocks, _src, scale = hier_mix_schedule("pushsum", t, 1, K, S,
                                                fl.topology)
        blocks0 = jnp.asarray(blocks[0], jnp.float32)  # [S, L, L]
        scale0 = jnp.asarray(scale[0], jnp.float32)    # [K]
        q, r = divmod(shift, L)

        def body(x, w, blk, sc):
            # per-pod view: x [L, D], w [L], blk [1, L, L], sc [L]
            intra = jnp.einsum("ij,jd->id", blk[0], x)
            wm = jnp.einsum("ij,j->i", blk[0], w)

            def from_pods_back(offset, arr):
                # deliver pod (s - offset)'s block to pod s; offset ≡ 0
                # (mod S) is the pod's own block — no collective
                if offset % S == 0:
                    return arr
                perm = [(p, (p + offset) % S) for p in range(S)]
                return jax.lax.ppermute(arr, "pod", perm)

            ax, aw = from_pods_back(q, x), from_pods_back(q, w)
            if r:
                # client j's source j-σ straddles two source shards when
                # σ is not a multiple of L: last r rows come from one pod
                # further back
                bx, bw = from_pods_back(q + 1, x), from_pods_back(q + 1, w)
                rx = jnp.concatenate([bx[L - r:], ax[:L - r]], axis=0)
                rw = jnp.concatenate([bw[L - r:], aw[:L - r]], axis=0)
            else:
                rx, rw = ax, aw
            # sc is zero on rows whose σ-edge stayed intra-shard (those
            # rows were already mixed by the block matmul above)
            return intra + sc[:, None] * rx, wm + sc * rw

        sm = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("pod"), P("pod"), P("pod"), P("pod")),
                           out_specs=(P("pod"), P("pod")), check_vma=False)
        return lambda flat, w: sm(flat, w, blocks0, scale0)

    exchanges = [make_exchange(t0 + i) for i in range(n_rounds)]

    def block_step(stacked_state, stacked_batch, keys):
        ms = []
        for i, ex in enumerate(exchanges):
            round_keys = jax.vmap(
                lambda kk: jax.random.fold_in(kk, t0 + i))(keys)
            new_state, m = jax.vmap(dml)(stacked_state, stacked_batch,
                                         round_keys)
            if ex is not None:
                theta = new_state["proxy"]["params"]
                flat = jax.vmap(tree_flatten_vector)(theta)   # [K, D]
                mixed, w2 = ex(flat, new_state["w"])
                unb = mixed / jnp.maximum(w2, 1e-9)[:, None]  # de-bias θ/w
                theta2 = jax.vmap(lambda v: tree_unflatten_vector(
                    v, jax.tree_util.tree_map(lambda a: a[0], theta)))(unb)
                new_state = dict(new_state)
                new_state["proxy"] = dict(new_state["proxy"], params=theta2)
                new_state["w"] = w2
            stacked_state = new_state
            ms.append(m)
        metrics = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ms)
        return stacked_state, metrics

    return block_step


# ---------------------------------------------------------------------------
# serve steps (private model inference)


def _serve_act_spec(opts: StepOptions):
    # 2D weight-stationary serving: residual stream [B, S, d] with d
    # sharded over "data" (sequence-parallel style) so matmuls against
    # (data × model)-sharded weights psum small partials instead of
    # gathering weights
    return (None, None, "data") if opts.serve_2d else None


def make_prefill_step(cfg: ModelConfig, opts: StepOptions = StepOptions()):
    def prefill(state, batch):
        tokens = batch["tokens"]
        img = batch.get("img")
        logits, cache, _ = forward(state["params"], cfg, tokens, img,
                                   cache=state["cache"], pos_offset=0,
                                   kv_chunk=opts.kv_chunk,
                                   mamba_chunk=opts.mamba_chunk,
                                   act_spec=_serve_act_spec(opts),
                                   moe_expert_axis="model" if (
                                       opts.shard_acts and opts.expert_parallel)
                                   else None)
        return {"params": state["params"], "cache": cache}, logits[:, -1]

    return prefill


def make_decode_step(cfg: ModelConfig, opts: StepOptions = StepOptions()):
    def decode(state, batch):
        tokens = batch["tokens"]          # [B, 1] (or [B, 1, K] audio)
        pos = batch["pos"]                # scalar int32 — current length
        logits, cache, _ = forward(state["params"], cfg, tokens,
                                   cache=state["cache"], pos_offset=pos,
                                   kv_chunk=opts.kv_chunk,
                                   mamba_chunk=opts.mamba_chunk,
                                   act_spec=_serve_act_spec(opts),
                                   moe_expert_axis="model" if (
                                       opts.shard_acts and opts.expert_parallel)
                                   else None)
        return {"params": state["params"], "cache": cache}, logits[:, -1]

    return decode


# ---------------------------------------------------------------------------
# sharding assembly


def train_shardings(mesh, state_shapes, batch_shapes, *, n_clients: int = 0,
                    expert_parallel: bool = False, modes: Optional[Dict] = None):
    """Per-model placement: ``choose_mode`` picks tp / zero1 / zero3 from the
    replicated-copy size (see sharding.py). ``modes`` overrides per role."""
    from .sharding import choose_mode, tree_pspecs as _tp

    cs = n_clients > 0
    modes = modes or {}
    state_spec: Dict = {}
    for role in ("private", "proxy"):
        p_shapes = state_shapes[role]["params"]
        mode = modes.get(role) or choose_mode(p_shapes, mesh)
        kw = dict(client_stacked=cs, expert_parallel=expert_parallel)
        state_spec[role] = {
            "params": _tp(p_shapes, mesh, fsdp_data=(mode == "zero3"), **kw),
            "opt": _tp(state_shapes[role]["opt"], mesh,
                       fsdp_data=(mode in ("zero1", "zero3")), **kw),
            "_mode": mode,
        }
    lead = P("pod") if cs and "pod" in mesh.axis_names else P()
    state_spec["w"] = lead
    state_spec["t"] = lead
    resolved = {r: state_spec[r].pop("_mode") for r in ("private", "proxy")}
    batch_spec = batch_pspecs(batch_shapes, mesh, client_stacked=cs)
    return state_spec, batch_spec, resolved


def serve_shardings(mesh, state_shapes, batch_shapes, *,
                    expert_parallel: bool = False, serve_2d: bool = False):
    from .sharding import choose_mode, tree_pspecs as _tp

    if serve_2d:
        # weight-stationary 2D TP: weights sharded over data AND model,
        # batch replicated over data, KV sequence sharded over data
        params_spec = _tp(state_shapes["params"], mesh,
                          expert_parallel=expert_parallel, fsdp_data=True)
        cache_spec = cache_pspecs(state_shapes["cache"], mesh,
                                  batch_replicated=True)
        batch_spec = jax.tree_util.tree_map(
            lambda l: P(*([None] * jnp.ndim(l))), batch_shapes)
        return {"params": params_spec, "cache": cache_spec}, batch_spec

    # default: never FSDP unless the replicated copy cannot fit (zero3-style
    # per-step gathers are hostile to decode latency)
    mode = choose_mode(state_shapes["params"], mesh)
    params_spec = _tp(state_shapes["params"], mesh,
                      expert_parallel=expert_parallel,
                      fsdp_data=(mode == "zero3"))
    cache_spec = cache_pspecs(state_shapes["cache"], mesh)
    batch_spec = batch_pspecs(batch_shapes, mesh)
    return {"params": params_spec, "cache": cache_spec}, batch_spec
