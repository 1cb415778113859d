"""PushSum gossip on time-varying directed graphs (paper §3.4).

The communication graph P^(t) is column-stochastic; every round each client
sends (P_{k',k} θ_k, P_{k',k} w_k) to out-neighbours, sums what it receives,
and de-biases by θ/w (Kempe et al. 2003; Nedić et al. 2018). With the
exponential protocol of Assran et al. (2019) each client has exactly ONE
out-neighbour per round — 2^(t mod ⌈log2 K⌉) hops away — so per-round
communication is O(1) in the number of clients (the paper's Fig. 4 claim).

Two execution backends:

* **simulation** — stacked client parameters, one matmul Θ ← P Θ per round
  (runs anywhere, used by the paper-reproduction benchmarks);
* **distributed** — inside ``shard_map`` over a mesh axis holding one
  client per device/pod, the same exchange is a single
  ``jax.lax.ppermute`` (the TPU-native realization of the MPI send/recv).
"""
from __future__ import annotations

import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def mix_dot(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """An exchange matmul at full f32 precision. A TPU's default precision
    for f32 operands is one bf16 pass, which would round every mixed proxy
    to bf16 in every round."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def exponential_offsets(n_clients: int) -> List[int]:
    """Peer offsets 2^0, 2^1, ..., 2^⌊log2(K-1)⌋ (Assran et al. 2019)."""
    if n_clients <= 1:
        return [0]
    return [2 ** p for p in range(int(math.floor(math.log2(n_clients - 1))) + 1)]


def gossip_shift(t: int, n_clients: int, topology: str = "exponential") -> int:
    if n_clients <= 1:
        return 0
    if topology == "exponential":
        offs = exponential_offsets(n_clients)
        return offs[t % len(offs)]
    if topology == "ring":
        return 1
    if topology == "full":
        return -1  # sentinel: dense averaging
    raise ValueError(topology)


def adjacency_matrix(t: int, n_clients: int, topology: str = "exponential",
                     self_weight: float = 0.5, active=None) -> np.ndarray:
    """Column-stochastic P^(t): column k holds the weights client k SENDS.

    ``active`` (bool mask, len K) drops clients out of the round (paper
    §3.4: the time-varying graph "can adapt to clients joining or dropping
    out"): inactive clients keep their own state (P_kk = 1) and neither
    send nor receive; the exponential/ring shift is applied on the ACTIVE
    subset so the graph stays connected. Column-stochasticity — and
    therefore PushSum's mass conservation and de-biased convergence to the
    average of the ACTIVE participants — is preserved.
    """
    K = n_clients
    if K == 1:
        return np.ones((1, 1))
    if active is None:
        active_idx = np.arange(K)
    else:
        active = np.asarray(active, bool)
        assert active.shape == (K,)
        active_idx = np.where(active)[0]
    A = len(active_idx)
    P = np.eye(K)  # inactive clients: identity column
    if A <= 1:
        return P
    shift = gossip_shift(t, A, topology)
    if shift == -1:  # dense uniform mixing among active
        for a_pos, k in enumerate(active_idx):
            P[k, k] = 0.0
            for b_pos, j in enumerate(active_idx):
                P[j, k] = 1.0 / A
    else:
        for a_pos, k in enumerate(active_idx):
            P[k, k] = self_weight
            peer = active_idx[(a_pos + shift) % A]
            P[peer, k] += 1.0 - self_weight
    assert np.allclose(P.sum(axis=0), 1.0)
    return P


# ---------------------------------------------------------------------------
# simulation backend: Θ^(t+1) = P^(t) Θ^(t)


def pushsum_mix(thetas: jnp.ndarray, weights: jnp.ndarray, P: jnp.ndarray,
                *, use_pallas: bool = False, interpret=None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """thetas: [K, D] stacked client vectors; weights: [K] de-bias weights.
    Returns mixed (thetas, weights) — NOT yet de-biased.

    ``use_pallas=True`` routes through the fused blocked kernel
    (:func:`repro.kernels.pushsum_mix.fused_pushsum_mix`, f32 accumulation,
    one HBM→VMEM pass per parameter chunk); allclose to the plain matmuls."""
    if use_pallas:
        from ..kernels.pushsum_mix import fused_pushsum_mix
        return fused_pushsum_mix(thetas, weights, P, debias=False,
                                 interpret=interpret)
    P = jnp.asarray(P, thetas.dtype)
    return mix_dot(P, thetas), mix_dot(P.astype(weights.dtype), weights)


def pushsum_mix_debiased(thetas: jnp.ndarray, weights: jnp.ndarray,
                         P: jnp.ndarray, *, use_pallas: bool = False,
                         interpret=None, compress=None, ef_state=None,
                         key=None):
    """The engine's whole stacked exchange (Algorithm 1 lines 7-11):
    ``z' = (P·z) / (P·w)[:, None]``, ``w' = P·w`` — mix AND de-bias.

    This is the single dispatch point the ``FederationEngine`` sync
    backends call: plain XLA (two matmuls + divide, the reference
    semantics) or the Pallas-fused kernel with the de-bias fused into the
    same pass (``use_pallas``, per ``ProxyFLConfig.use_pallas``).

    ``compress`` (a :class:`repro.core.compress.CompressionSpec`) routes
    the exchange through the compressed protocol instead: each sender
    transmits a compressed DELTA against its public copy ``ef_state``
    [K, D] (``key`` feeds int8's stochastic rounding), receivers mix the
    updated dense copies, and the call returns a THREE-tuple
    ``(z', w', ef_state')``. The Pallas kernels
    implement the uncompressed chain only, so the compressed branch always
    takes the plain-XLA path and ``use_pallas`` is ignored (documented in
    ``core.compress``). ``compress=None`` keeps this function — and its
    compiled program — byte-for-byte the uncompressed exchange."""
    if compress is not None:
        from .compress import compressed_pushsum_mix
        return compressed_pushsum_mix(thetas, weights, P, ef_state, key,
                                      compress)
    if use_pallas:
        from ..kernels.pushsum_mix import fused_pushsum_mix
        return fused_pushsum_mix(thetas, weights, P, debias=True,
                                 interpret=interpret)
    mixed = mix_dot(jnp.asarray(P, thetas.dtype), thetas)
    w2 = mix_dot(jnp.asarray(P, weights.dtype), weights)
    return mixed / w2[:, None], w2


def stale_mix_apply(flat: jnp.ndarray, w: jnp.ndarray, kept: jnp.ndarray,
                    sent: jnp.ndarray, buf_t0: jnp.ndarray,
                    buf_w0: jnp.ndarray, *, use_pallas: bool = False,
                    interpret=None, compress=None, ef_state=None, key=None):
    """One stale (async τ>0) exchange on the stacked proxies — the
    delayed-delivery counterpart of :func:`pushsum_mix_debiased` and the
    on-device application of :func:`stale_gossip_reference`'s round body:
    re-bias θ = z·w, emit ``send = sent @ θ``, merge ``kept·θ`` with the
    delivery ``buf_t0``/``buf_w0`` rotating out of the in-flight buffer,
    de-bias by the identically-delayed weights. Returns ``(z', send_t,
    w', send_w)``; the caller owns the buffer rotation. ``use_pallas``
    fuses the whole chain into one blocked pass per parameter chunk
    (:func:`repro.kernels.pushsum_mix.fused_stale_mix`).

    ``compress``/``ef_state``/``key`` route the in-flight transmission
    (public-copy delta coding on the numerator θ)
    through the codec with error feedback exactly as in
    :func:`pushsum_mix_debiased` — the return grows a trailing ``ef_state'``
    (five-tuple) and ``use_pallas`` is ignored (the fused kernel is
    uncompressed-only; see ``core.compress``)."""
    if compress is not None:
        from .compress import compressed_stale_mix
        return compressed_stale_mix(flat, w, kept, sent, buf_t0, buf_w0,
                                    ef_state, key, compress)
    if use_pallas:
        from ..kernels.pushsum_mix import fused_stale_mix
        return fused_stale_mix(flat, w, kept, sent, buf_t0, buf_w0,
                               interpret=interpret)
    theta = flat * w[:, None]                  # raw PushSum numerator
    send_t = mix_dot(sent.astype(flat.dtype), theta)
    send_w = mix_dot(sent.astype(w.dtype), w)
    mixed = kept.astype(flat.dtype)[:, None] * theta + buf_t0
    w2 = kept.astype(w.dtype) * w + buf_w0
    return mixed / w2[:, None], send_t, w2, send_w


def mix_matrix(mix: str, t: int, n_clients: int, topology: str = "exponential",
               active=None, self_weight: float = 0.5) -> np.ndarray:
    """Column-stochastic mixing matrix for ONE federated exchange.

    Every aggregation rule in the METHODS table is a K×K column-stochastic
    matrix applied to the stacked client vectors (plus PushSum de-biasing,
    which is the identity whenever the matrix keeps w at 1):

    * ``"pushsum"`` — the paper's §3.4 time-varying graph P^(t) (ProxyFL,
      AvgPush);
    * ``"mean"``    — uniform averaging among active clients (FedAvg, FML's
      central proxy server);
    * ``"ring"``    — cyclical weight transfer: a pure permutation, client k
      receives client k-1's model (CWT);
    * ``"none"``    — no exchange (Regular / Joint).

    ``active`` masks out dropped clients exactly as in
    :func:`adjacency_matrix`: they keep their own state (identity column)
    and neither send nor receive.
    """
    if mix == "none":
        return np.eye(n_clients)
    if mix == "pushsum":
        return adjacency_matrix(t, n_clients, topology, self_weight, active)
    if mix == "mean":
        return adjacency_matrix(t, n_clients, "full", self_weight, active)
    if mix == "ring":
        return adjacency_matrix(t, n_clients, "ring", 0.0, active)
    raise ValueError(mix)


def debias(thetas: jnp.ndarray, weights: jnp.ndarray) -> jnp.ndarray:
    """θ_k / w_k (Algorithm 1 line 11)."""
    return thetas / weights[:, None]


# ---------------------------------------------------------------------------
# block schedules: P^(t0), ..., P^(t0+T-1) precomputed for a round-block


def shift_schedule(t0: int, T: int, n_active: int,
                   topology: str = "exponential") -> np.ndarray:
    """int[T] gossip shifts for rounds t0..t0+T-1 over ``n_active`` peers
    (-1 is the dense sentinel, matching :func:`gossip_shift`)."""
    ts = np.arange(t0, t0 + T)
    if n_active <= 1:
        return np.zeros(T, np.int64)
    if topology == "exponential":
        offs = np.asarray(exponential_offsets(n_active))
        return offs[ts % len(offs)]
    if topology == "ring":
        return np.ones(T, np.int64)
    if topology == "full":
        return -np.ones(T, np.int64)
    raise ValueError(topology)


def adjacency_schedule(t0: int, T: int, n_clients: int,
                       topology: str = "exponential",
                       self_weight: float = 0.5, active=None) -> np.ndarray:
    """Stacked column-stochastic P^(t0..t0+T-1): float64[T, K, K], with
    ``P[i] == adjacency_matrix(t0 + i, ...)`` exactly.

    ``active`` is None (everyone, every round) or bool[T, K] — one §3.4
    membership row per round. Construction is vectorized: rounds sharing a
    membership pattern are built together with batched scatters (no
    per-client Python loops), so a round-block's whole schedule costs a
    handful of numpy ops instead of T × K loop iterations.
    """
    K = n_clients
    P = np.broadcast_to(np.eye(K), (T, K, K)).copy()
    if K == 1 or T == 0:
        return P
    ts = np.arange(t0, t0 + T)
    if active is None:
        groups = [(np.arange(K), np.arange(T))]
    else:
        active = np.asarray(active, bool)
        assert active.shape == (T, K), (active.shape, (T, K))
        patterns, inverse = np.unique(active, axis=0, return_inverse=True)
        groups = [(np.where(patterns[g])[0], np.where(inverse == g)[0])
                  for g in range(len(patterns))]
    for idx, rows in groups:
        A = len(idx)
        if A <= 1:
            continue  # inactive-heavy round: identity (already in place)
        if topology == "exponential":
            offs = np.asarray(exponential_offsets(A))
            shifts = offs[ts[rows] % len(offs)]
        elif topology == "ring":
            shifts = np.ones(len(rows), np.int64)
        elif topology == "full":
            shifts = -np.ones(len(rows), np.int64)
        else:
            raise ValueError(topology)
        dense = shifts == -1
        if dense.any():
            P[np.ix_(rows[dense], idx, idx)] = 1.0 / A
        sparse = np.where(~dense)[0]
        if len(sparse):
            r = np.repeat(rows[sparse], A)
            col = np.tile(idx, len(sparse))
            P[r, col, col] = self_weight
            pos = np.arange(A)
            peers = idx[(pos[None, :] + shifts[sparse, None]) % A]
            np.add.at(P, (r, peers.reshape(-1), col), 1.0 - self_weight)
    assert np.allclose(P.sum(axis=1), 1.0)  # column-stochastic, every round
    return P


def mix_schedule(mix: str, t0: int, T: int, n_clients: int,
                 topology: str = "exponential", active=None,
                 self_weight: float = 0.5) -> np.ndarray:
    """Stacked mixing matrices for one round-block: float64[T, K, K] with
    ``out[i] == mix_matrix(mix, t0 + i, ...)`` exactly (same mix -> graph
    mapping as :func:`mix_matrix`; ``active`` is None or bool[T, K]).

    This is the host-side half of the engine's fused round-block execution:
    instead of re-entering Python every round to build P^(t), a block's
    whole schedule is precomputed once and fed to the compiled scan as one
    [T, K, K] runtime argument."""
    if mix == "none":
        return np.broadcast_to(np.eye(n_clients), (T, n_clients, n_clients)).copy()
    if mix == "pushsum":
        return adjacency_schedule(t0, T, n_clients, topology, self_weight,
                                  active)
    if mix == "mean":
        return adjacency_schedule(t0, T, n_clients, "full", self_weight,
                                  active)
    if mix == "ring":
        return adjacency_schedule(t0, T, n_clients, "ring", 0.0, active)
    raise ValueError(mix)


# ---------------------------------------------------------------------------
# stale gossip: the async backend's diag/off-diag split of P^(t)
#
# The synchronous exchange applies the whole column-stochastic P^(t) at
# once. The staleness-τ variant (Assran et al. 2019's overlap trick) splits
# every column into the mass a client KEEPS (the diagonal) and the mass it
# SENDS (the off-diagonal rest): sends computed at round t stay in flight —
# communication overlapped with the next local scans — and are delivered at
# round t+τ. Crucially the split operates on the RAW PushSum numerators
# θ = z·w (not the de-biased z): the de-bias weights w then account for the
# in-flight mass exactly, so θ/w stays a proper weighted average of client
# parameters at every staleness, and total θ- and w-mass (clients + buffer)
# is conserved round by round (column-stochasticity is preserved by the
# split: kept_k + Σ_j sent_{jk} = Σ_j P_{jk} = 1).


def stale_mix_split(P):
    """Diag/off-diag split of column-stochastic matrices (batched over any
    leading dims): returns ``(kept[..., K], sent[..., K, K])`` with
    ``P == sent + diag_embed(kept)`` exactly — ``kept[k]`` is the mass
    client k retains this round, column ``sent[:, k]`` the mass it puts in
    flight."""
    P = np.asarray(P)
    K = P.shape[-1]
    idx = np.arange(K)
    kept = P[..., idx, idx].copy()
    sent = P.copy()
    sent[..., idx, idx] = 0.0
    return kept, sent


def stale_mix_schedule(mix: str, t0: int, T: int, n_clients: int,
                       topology: str = "exponential", active=None,
                       self_weight: float = 0.5):
    """Stacked stale-mix split for one round-block: ``(kept[T, K],
    sent[T, K, K])`` with ``sent[i] + diag(kept[i]) == mix_matrix(mix,
    t0 + i, ...)`` exactly (same mix -> graph mapping, ``active`` is None
    or bool[T, K]). The host-side half of the async backend's fused
    round-block execution."""
    return stale_mix_split(mix_schedule(mix, t0, T, n_clients, topology,
                                        active=active,
                                        self_weight=self_weight))


def stale_gossip_reference(z0, w0, Ps, staleness: int):
    """Numpy reference of the staleness-τ PushSum exchange — the executable
    spec the async engine backend and its property tests are held to.

    ``z0``: [K, D] de-biased client vectors; ``w0``: [K] de-bias weights;
    ``Ps``: iterable of [K, K] column-stochastic matrices (one per round,
    §3.4 active masking already applied). Per round t:

    1. re-bias:  θ(t) = z(t) · w(t)  (raw PushSum numerators);
    2. send:     ``sent(t) @ θ(t)`` and ``sent(t) @ w(t)`` enter a τ-deep
       in-flight buffer (delivered at round t+τ; the buffer starts empty —
       for the first τ rounds nothing arrives and the de-bias weights
       shrink to account for the mass in flight);
    3. deliver:  the round-(t−τ) sends leave the buffer and merge into
       ``mixed = kept(t)·θ(t) + recv`` and ``w' = kept(t)·w(t) + recv_w``;
    4. de-bias:  z(t+1) = mixed / w'.

    τ=0 degenerates to the synchronous exchange ``P @ θ`` / ``P @ w``.
    Returns ``(z, w, buf_theta[τ, K, D], buf_w[τ, K])`` after ``len(Ps)``
    rounds; buffer row 0 is the next delivery. Invariants (property-tested
    in tests/test_gossip.py): Σ w + Σ buf_w == Σ w0 and
    Σ z·w + Σ buf_theta == Σ z0·w0 after every round, for ANY τ and any
    §3.4 dropout trajectory; a send entered at round t leaves the buffer
    at exactly round t+τ."""
    z = np.asarray(z0, np.float64)
    w = np.asarray(w0, np.float64)
    K, D = z.shape
    tau = int(staleness)
    buf_t = np.zeros((tau, K, D))
    buf_w = np.zeros((tau, K))
    for P in Ps:
        kept, sent = stale_mix_split(np.asarray(P, np.float64))
        theta = z * w[:, None]
        if tau == 0:
            mixed = (sent + np.diag(kept)) @ theta
            w = (sent + np.diag(kept)) @ w
        else:
            send_t, send_w = sent @ theta, sent @ w
            mixed = kept[:, None] * theta + buf_t[0]
            w = kept * w + buf_w[0]
            buf_t = np.concatenate([buf_t[1:], send_t[None]])
            buf_w = np.concatenate([buf_w[1:], send_w[None]])
        z = mixed / w[:, None]
    return z, w, buf_t, buf_w


# ---------------------------------------------------------------------------
# hierarchical gossip: the hier backend's two-level factoring of P^(t)
#
# A two-level cohort of S shards × L clients-per-shard executes the SAME
# flat column-stochastic schedule P^(t), factored by edge locality instead
# of applied as one dense [K, K] matmul: the entries whose sender and
# receiver share a shard form a block-diagonal [S, L, L] part (applied as S
# independent [L, L] × [L, D] matmuls — the on-device mix, O(K·L·D) instead
# of O(K²·D)), and the cross-shard entries form a sparse scaled permutation
# (each client sends to at most ONE peer per round under the exponential/
# ring protocols — exactly the structure a `ppermute` collective realizes
# on a device mesh, and exactly the per-client O(1) bytes-on-wire claim).
# The split is a SUM decomposition, P = blockdiag + cross, so rebuilding is
# exact (disjoint supports): the factored application moves the same mass
# as the flat matmul, and mass conservation / column-stochasticity are
# inherited from P. Staleness applies to the cross part only: delayed
# cross-shard deliveries ride the same τ-deep in-flight buffer algebra as
# :func:`stale_gossip_reference` while the intra-shard exchange stays
# synchronous (the "inter-pod latency absorbed by the async τ-buffer"
# deployment of ROADMAP's thousand-client item).


def hier_layout(n_clients: int, n_shards: int) -> Tuple[int, int]:
    """Validated two-level cohort layout ``(S, L)``: client k lives in
    shard ``k // L`` at local index ``k % L``. ``n_shards`` must divide the
    cohort evenly — ragged shard sizes would need per-shard block shapes
    and break the single batched intra-shard matmul."""
    S = 1 if n_shards is None else int(n_shards)
    if S < 1 or S > n_clients or n_clients % S:
        raise ValueError(
            f"n_shards={n_shards} must evenly divide n_clients="
            f"{n_clients} (two-level [shards × clients-per-shard] cohort)")
    return S, n_clients // S


def hier_mix_split(P, n_shards: int):
    """Factor one flat column-stochastic ``P`` [K, K] by edge locality.

    Returns ``(blocks[S, L, L], src[K], scale[K])``:

    * ``blocks[s]`` — P restricted to shard s's intra-shard edges
      (diagonal included);
    * ``src[i]`` / ``scale[i]`` — the one cross-shard in-edge of client i
      (``P[i, src[i]] == scale[i]``), or ``src[i] == i, scale[i] == 0``
      when none. Cross-shard deliveries are therefore a gather + scale —
      the simulation form of a ``ppermute`` + scale on a real mesh.

    The decomposition is EXACT (disjoint supports):
    ``blockdiag(blocks) + scatter(src, scale) == P`` bitwise — proven by
    tests/test_gossip.py against :func:`mix_schedule`. Raises when the
    cross-shard part is not a scaled partial permutation (≥2 cross
    in/out-edges per client — e.g. dense "full"/mean mixing), which is not
    hier-factorable: there is no O(1) collective schedule for it."""
    P = np.asarray(P)
    K = P.shape[-1]
    S, L = hier_layout(K, n_shards)
    shard = np.arange(K) // L
    intra = shard[:, None] == shard[None, :]
    cross = np.where(intra, 0.0, P)
    if (np.count_nonzero(cross, axis=1) > 1).any() or \
            (np.count_nonzero(cross, axis=0) > 1).any():
        raise ValueError(
            "hier factoring needs at most one cross-shard edge per client "
            "per round (a scaled partial permutation); dense mixing "
            "(topology='full' / mix='mean') is not hier-factorable")
    blocks = np.where(intra, P, 0.0).reshape(S, L, S, L)
    blocks = blocks[np.arange(S), :, np.arange(S), :]          # [S, L, L]
    src = np.argmax(cross != 0.0, axis=1)
    has = cross[np.arange(K), src] != 0.0
    src = np.where(has, src, np.arange(K))
    scale = np.where(has, cross[np.arange(K), src], 0.0)
    return blocks, src.astype(np.int64), scale


def hier_mix_schedule(mix: str, t0: int, T: int, n_clients: int,
                      n_shards: int, topology: str = "exponential",
                      active=None, self_weight: float = 0.5):
    """Stacked two-level factoring of one round-block's flat schedule:
    ``(blocks[T, S, L, L], src[T, K], scale[T, K])`` with each round's
    rebuilt ``blockdiag(blocks[i]) + scatter(src[i], scale[i])`` equal —
    bitwise — to ``mix_schedule(mix, t0, T, ...)[i]``. Same mix -> graph
    mapping and §3.4 ``active`` handling (None or bool[T, K]) as
    :func:`mix_schedule`; the host-side half of the hier backend's fused
    round-block execution."""
    Ps = mix_schedule(mix, t0, T, n_clients, topology, active=active,
                      self_weight=self_weight)
    parts = [hier_mix_split(Ps[i], n_shards) for i in range(T)]
    blocks = np.stack([p[0] for p in parts])
    src = np.stack([p[1] for p in parts])
    scale = np.stack([p[2] for p in parts])
    return blocks, src, scale


def _hier_intra(x, w, blocks, use_pallas, interpret):
    """Block-diagonal half of one factored exchange: S independent
    [L, L] × [L, D] shard-local matmuls over the stacked vectors (plus the
    matching w mix) — ``use_pallas`` routes each shard's matmul through the
    fused blocked kernel (the [L, L] block resident in VMEM, vmapped over
    the shard axis)."""
    S, L, _ = blocks.shape
    xs = x.reshape(S, L, -1)
    ws = w.reshape(S, L)
    if use_pallas:
        from ..kernels.pushsum_mix import fused_pushsum_mix
        mixed, wm = jax.vmap(lambda f, ww, p: fused_pushsum_mix(
            f, ww, p, debias=False, interpret=interpret))(xs, ws, blocks)
    else:
        Pb = jnp.asarray(blocks, x.dtype)
        mixed = mix_dot(Pb, xs)
        wm = mix_dot(Pb.astype(w.dtype), ws[..., None])[..., 0]
    return mixed.reshape(x.shape), wm.reshape(w.shape)


def hier_mix_debiased(flat, w, blocks, src, scale, *, use_pallas=False,
                      interpret=None):
    """One SYNCHRONOUS factored exchange on the stacked proxies — the
    two-level application of :func:`pushsum_mix_debiased`'s
    ``z' = (P·z) / (P·w)``: shard-local block matmuls plus the scaled
    cross-shard gather (the simulation form of a ``ppermute`` delivery).
    Because every client has at most one cross-shard in-edge and the
    rebuilt P is exact, the result is BITWISE equal to the flat dense
    exchange on the same P (each output row performs the same ≤2 real
    additions; zero terms add exactly) — enforced by
    tests/test_conformance.py's hier-τ0 == vmap columns."""
    mixed, wm = _hier_intra(flat, w, blocks, use_pallas, interpret)
    s = jnp.asarray(scale, flat.dtype)
    mixed = mixed + s[:, None] * flat[src]
    w2 = wm + s.astype(w.dtype) * w[src]
    return mixed / w2[:, None], w2


def hier_stale_mix_apply(flat, w, blocks, src, scale, buf_t0, buf_w0, *,
                         use_pallas=False, interpret=None):
    """One STALE (τ>0) factored exchange: the on-device application of
    :func:`hier_gossip_reference`'s round body. Re-bias θ = z·w, mix the
    intra-shard part synchronously, emit the cross-shard send
    ``scale·θ[src]`` (the caller pushes it into the τ-deep buffer and owns
    the rotation, exactly as with :func:`stale_mix_apply`), merge the
    round-(t−τ) delivery ``buf_t0``/``buf_w0``, and de-bias by the
    identically-delayed weights. Returns ``(z', send_t, w', send_w)``.
    Only cross-shard mass is ever stale — the intra-shard matmul reads the
    CURRENT θ."""
    theta = flat * w[:, None]                  # raw PushSum numerator
    mixed, wm = _hier_intra(theta, w, blocks, use_pallas, interpret)
    s = jnp.asarray(scale, flat.dtype)
    send_t = s[:, None] * theta[src]
    send_w = s.astype(w.dtype) * w[src]
    w2 = wm + buf_w0
    return (mixed + buf_t0) / w2[:, None], send_t, w2, send_w


def hier_gossip_reference(z0, w0, Ps, n_shards: int, staleness: int = 0):
    """Numpy reference of the two-level (hier) PushSum exchange — the
    executable spec the hier engine backend and its property tests are
    held to, mirroring :func:`stale_gossip_reference`. Per round t, with
    ``blocks/src/scale = hier_mix_split(P(t), n_shards)``:

    1. re-bias:   θ(t) = z(t) · w(t);
    2. intra mix: ``mixed = blockdiag(blocks) @ θ`` — S independent
       [L, L] × [L, D] shard-local matmuls, always synchronous;
    3. cross send: client i's one cross-shard in-edge delivers
       ``scale[i] · θ[src[i]]`` — immediately at τ=0, or through a τ-deep
       in-flight buffer at τ>0 (ONLY the cross-shard mass is ever stale);
    4. merge + de-bias: z(t+1) = (mixed + delivery) / (w-mixed + w-delivery).

    Invariants (tested in tests/test_gossip.py): Σ w + Σ buf_w == Σ w0 and
    Σ z·w + Σ buf == Σ z0·w0 after every round for any τ, n_shards and
    §3.4 dropout trajectory; at τ=0 the trajectory equals the flat
    synchronous :func:`stale_gossip_reference` (staleness 0) bit-for-bit —
    the factored application of P moves identical mass because every
    client has at most one cross-shard in-edge (a single extra addition
    against the shard-local partial row sum). Returns ``(z, w,
    buf_theta[τ, K, D], buf_w[τ, K])``; buffer row 0 is the next
    delivery."""
    z = np.asarray(z0, np.float64)
    w = np.asarray(w0, np.float64)
    K, D = z.shape
    S, L = hier_layout(K, n_shards)
    tau = int(staleness)
    buf_t = np.zeros((tau, K, D))
    buf_w = np.zeros((tau, K))
    for P in Ps:
        blocks, src, scale = hier_mix_split(np.asarray(P, np.float64),
                                            n_shards)
        theta = z * w[:, None]
        mixed = np.einsum("sij,sjd->sid", blocks,
                          theta.reshape(S, L, D)).reshape(K, D)
        wm = np.einsum("sij,sj->si", blocks, w.reshape(S, L)).reshape(K)
        send_t = scale[:, None] * theta[src]
        send_w = scale * w[src]
        if tau == 0:
            arrive_t, arrive_w = send_t, send_w
        else:
            arrive_t, arrive_w = buf_t[0], buf_w[0]
            buf_t = np.concatenate([buf_t[1:], send_t[None]])
            buf_w = np.concatenate([buf_w[1:], send_w[None]])
        w = wm + arrive_w
        z = (mixed + arrive_t) / w[:, None]
    return z, w, buf_t, buf_w


# ---------------------------------------------------------------------------
# distributed backend: one client per mesh-axis index, ppermute exchange


def pushsum_gossip_shard(theta_local: jnp.ndarray, w_local: jnp.ndarray,
                         t: int, axis: str, n_clients: int,
                         topology: str = "exponential",
                         self_weight: float = 0.5,
                         active=None):
    """Inside shard_map: one PushSum round along mesh axis ``axis``.

    Sends (1-self_weight)·(θ, w) to the peer ``shift`` ahead; keeps
    self_weight·(θ, w). Exactly Algorithm 1 lines 7-10 with P^(t) from
    :func:`adjacency_matrix`, realized as a collective-permute (cost
    independent of K — the O(1) communication claim).

    ``active`` (static bool sequence, len K) is the §3.4 dropout/join mask:
    inactive clients keep their state untouched, the permutation runs over
    the ACTIVE subset only (so the graph stays connected), and dense
    ("full") mixing becomes a masked psum over active participants. The
    mask is trace-time static — each distinct pattern is its own compiled
    collective schedule, matching how a real deployment would re-plan its
    communication graph on membership changes."""
    if active is None:
        active_idx = list(range(n_clients))
    else:
        assert len(active) == n_clients
        active_idx = [i for i in range(n_clients) if active[i]]
    A = len(active_idx)
    if A <= 1:
        return theta_local, w_local
    shift = gossip_shift(t, A, topology)
    if shift == 0:
        return theta_local, w_local
    amask = np.zeros((n_clients,), np.float32)
    amask[active_idx] = 1.0
    idx = jax.lax.axis_index(axis)
    m = jnp.asarray(amask)[idx].astype(theta_local.dtype)
    if shift == -1:  # dense averaging among active (AvgPush-full / FedAvg)
        sum_t = jax.lax.psum(m * theta_local, axis)
        sum_w = jax.lax.psum(m * w_local, axis)
        return (m * sum_t / A + (1.0 - m) * theta_local,
                m * sum_w / A + (1.0 - m) * w_local)
    perm = [(active_idx[p], active_idx[(p + shift) % A]) for p in range(A)]
    keep = 1.0 - m * (1.0 - self_weight)  # self_weight if active else 1
    send_t = (1.0 - self_weight) * theta_local
    send_w = (1.0 - self_weight) * w_local
    recv_t = jax.lax.ppermute(send_t, axis, perm)  # zeros at non-receivers
    recv_w = jax.lax.ppermute(send_w, axis, perm)
    return keep * theta_local + recv_t, keep * w_local + recv_w


# ---------------------------------------------------------------------------
# communication-cost model (paper Fig. 4 / Fig. 13)


def comm_cost_per_round(method: str, n_clients: int, model_bytes: int,
                        proxy_bytes: int, link_bandwidth: float = 50e9) -> float:
    """Analytic wall-clock communication time of ONE round (seconds).

    Centralized schemes serialize at the server: it receives K models and
    sends K back over one link (the bottleneck the paper measures).
    Decentralized schemes send/receive exactly one model per client in
    parallel. CWT passes one model around but rounds are serialized."""
    if method in ("fedavg",):
        return 2 * n_clients * model_bytes / link_bandwidth
    if method in ("fml",):
        return 2 * n_clients * proxy_bytes / link_bandwidth
    if method in ("avgpush", "cwt"):
        return 2 * model_bytes / link_bandwidth
    if method in ("proxyfl",):
        return 2 * proxy_bytes / link_bandwidth
    if method in ("regular", "joint"):
        return 0.0
    raise ValueError(method)
