"""Compressed proxy exchange — top-k / int8 gossip with error feedback.

Communication efficiency is the paper's headline claim (§4, Fig. 4:
ProxyFL sends exactly ONE proxy per client per round, O(1) in federation
size). This module shrinks that one proxy with CHOCO-SGD-style
public-copy delta coding (Koloskova et al. 2019; Stich et al. 2018):
each client maintains a PUBLIC COPY ``ẑ_k`` of its vector that every
receiver already holds, transmits only a compressed DELTA against it,
and receivers mix the updated — dense — copies. Truncated mass stays in
the implicit error-feedback residual ``m_k − ẑ_k``, re-transmitted in
later rounds, so compression delays information instead of destroying
it.

Why deltas-against-a-copy rather than zero-filling the sparse message
into the mix (the naive scheme): under PushSum the receiver divides by
the FULL mixed weight ``P @ w``, so a zero-filled coordinate is not
"skipped" — it is multiplied by ``kept/w ≈ 0.5`` every round it goes
untransmitted. Top-k at ratio 0.25 then shrinks 75 % of every received
vector toward zero each round and the proxies diverge (measured: a
25-point proxy-accuracy gap at K=16). With a public copy the receiver
always mixes a dense ``ẑ_j ≈ z_j``; sparsity only bounds how fast the
copy tracks the truth.

The protocol shape (one round, stacked [K, D] client vectors):

1. split the column-stochastic P^(t) into the mass each client KEEPS
   (``kept`` = diag) and the mass it SENDS (``sent`` = off-diag) — a
   client's own state never crosses the wire, so only senders encode;
2. delta: ``u_k = m_k − ẑ_k`` (this round's would-be transmission minus
   the copy receivers hold; the error-feedback residual IS ``u_k``);
3. encode/decode: ``c_k = C(u_k)`` — the DE-compressed transmitted
   delta (receivers apply exactly ``c_k``);
4. copy update, sender and receivers in lockstep: ``ẑ'_k = ẑ_k + c_k``.
   The conservation invariant ``c_k + (m_k − ẑ'_k) == m_k − ẑ_k`` —
   transmitted delta plus remaining residual equals the mass owed — is
   EXACT in f32 by construction (``m − ẑ' = u − c`` elementwise, and at
   coordinates the codec kept, ``u − c`` is the bf16/int8 rounding
   error; at dropped coordinates ``c = 0`` leaves ``u`` intact);
5. mix: receivers merge ``kept_k · m_k + Σ_j sent_{kj} · ẑ'_j`` (dense!)
   and de-bias by the (uncompressed — K floats are free) PushSum
   weights.

Clients that send NOTHING this round (§3.4 dropouts: identity column,
zero off-diagonal mass; or a no-exchange round) keep their public copy
UNTOUCHED — receivers could not have observed an update, so advancing
``ẑ`` without a transmission would desynchronize sender and receivers.

Copies WARM-START at the initial vectors (one uncompressed broadcast at
setup — the engine owns init, so receivers hold ``ẑ_0 = m_0`` before the
first compressed round; a cold ``ẑ_0 = 0`` start needs ≈1/ratio rounds
just to cover the coordinates and measurably lags at short horizons),
and a lossless codec gives ``ẑ' ≡ m`` so the scheme reduces to the
plain exchange.

Codecs (wire formats, measured by :func:`wire_bytes`):

``"topk"``
    Keep the ``k = ratio · D`` largest-magnitude entries of the delta
    per client, values rounded to bf16 on the wire, positions as a D-bit
    bitmap: ``D/8 + 2k`` bytes vs ``4D`` full-precision — ≥4x at ratio
    0.25 (6.4x). Deterministic (no RNG). Magnitude selection on the
    delta rotates coordinates naturally: whatever went untransmitted
    grows in ``u`` until it wins a slot.
``"int8"``
    Per-client scale ``s = max|u| / 127``; entries stochastically rounded
    to int8 (unbiased: round up with probability equal to the fractional
    part): ``D + 4`` bytes — ~4x. The rounding noise is drawn from the
    round key (:func:`compress_round_key`), so every backend and any
    kill/resume replays identical bits.
``"none"``
    Not a codec: the engine bypasses this module entirely and the plain
    exchange runs VERBATIM (bitwise-identical to the uncompressed
    protocol — enforced by tests/test_conformance.py).

``compressed_gossip_reference`` is the numpy executable spec of the
synchronous compressed exchange (the engine and its property tests are
held to it), mirroring ``stale_gossip_reference`` in ``core.gossip``;
``topk_reference``/``int8_reference``/``ef_encode_reference`` are the
per-op numpy oracles used by tests/test_compress.py.

Interplay with the Pallas-fused hot path: the fused kernels implement the
UNCOMPRESSED mix chains; when compression is on, the exchange takes the
plain-XLA compressed path regardless of ``use_pallas`` (documented
honestly — fusing the codec into the kernels is future work; local DP
steps still fuse).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .gossip import mix_dot

# compression RNG domain: the stochastic-rounding noise of round t is drawn
# from fold_in(round_key, COMPRESS_KEY_FOLD). The constant is far outside
# the engine's per-client fold domain (0..K-1) so codec noise can never
# collide with a client's local-step RNG chain.
COMPRESS_KEY_FOLD = 987_654_321

MODES = ("none", "topk", "int8")


@dataclass(frozen=True)
class CompressionSpec:
    """Static codec configuration (hashable — rides in jit closures)."""

    mode: str = "none"      # "topk" | "int8" ("none" never builds a spec)
    ratio: float = 0.25     # top-k kept fraction of D (ignored by int8)

    def __post_init__(self):
        assert self.mode in MODES, self.mode
        assert 0.0 < self.ratio <= 1.0, self.ratio


def compress_spec(cfg) -> Optional[CompressionSpec]:
    """``CompressionSpec`` from a ProxyFLConfig, or None for ``"none"``
    (None is the engine's signal to keep the uncompressed path verbatim)."""
    mode = getattr(cfg, "compress", "none") or "none"
    if mode == "none":
        return None
    return CompressionSpec(mode=mode,
                           ratio=float(getattr(cfg, "compress_ratio", 0.25)))


def compress_round_key(round_key):
    """Round t's codec RNG key under the canonical schedule — identical on
    every backend (loop folds the same round key the stacked scan folds),
    so loop/vmap/async draw the same stochastic-rounding bits."""
    return jax.random.fold_in(round_key, COMPRESS_KEY_FOLD)


def topk_k(D: int, ratio: float) -> int:
    """Entries kept per client: ``max(1, round(ratio · D))``, capped at D."""
    return max(1, min(int(round(ratio * D)), D))


# ---------------------------------------------------------------------------
# codecs: encode + immediately decode (simulation measures bytes, it does
# not ship them; ``c`` is exactly what a receiver would reconstruct)


def _topk_encode_decode(u: jnp.ndarray, k: int) -> jnp.ndarray:
    """Per-row top-k by |u| with bf16 wire values: dense [K, D] with zeros
    at dropped positions. f32 in, f32 out."""
    K = u.shape[0]
    _, idx = jax.lax.top_k(jnp.abs(u), k)
    mask = jnp.zeros(u.shape, bool).at[
        jnp.arange(K)[:, None], idx].set(True)
    wire = u.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.where(mask, wire, 0.0)


def _int8_encode_decode(u: jnp.ndarray, noise: jnp.ndarray) -> jnp.ndarray:
    """Per-row-scaled int8 stochastic rounding; ``noise`` ~ U[0,1) of
    u.shape decides each entry's round-up. f32 in, f32 out."""
    scale = jnp.maximum(jnp.max(jnp.abs(u), axis=1), 1e-12) / 127.0
    x = u / scale[:, None]
    lo = jnp.floor(x)
    q = lo + (noise < (x - lo)).astype(jnp.float32)
    q = jnp.clip(q, -127.0, 127.0)
    return q * scale[:, None]


def encode_decode(u: jnp.ndarray, key, spec: CompressionSpec) -> jnp.ndarray:
    """Decoded transmission ``C(u)`` for a stacked f32 [K, D] delta block
    (``key`` feeds int8's stochastic rounding; top-k ignores it)."""
    if spec.mode == "topk":
        return _topk_encode_decode(u, topk_k(u.shape[1], spec.ratio))
    if spec.mode == "int8":
        noise = jax.random.uniform(key, u.shape, jnp.float32)
        return _int8_encode_decode(u, noise)
    raise ValueError(spec.mode)


def wire_bytes(mode: str, D: int, ratio: float = 0.25,
               dtype_bytes: int = 4) -> int:
    """Bytes ONE client puts on the wire for one D-entry message.

    none: D full-precision values. topk: a D-bit position bitmap plus k
    bf16 values. int8: D bytes plus one f32 scale. De-bias weights (one
    float per client) are noise and excluded everywhere."""
    if mode == "none":
        return D * dtype_bytes
    if mode == "topk":
        return (D + 7) // 8 + 2 * topk_k(D, ratio)
    if mode == "int8":
        return D + 4
    raise ValueError(mode)


# ---------------------------------------------------------------------------
# compressed exchanges (dispatched from the gossip choke points)


def _split_P(Pf: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    idx = jnp.arange(Pf.shape[0])
    kept = Pf[idx, idx]
    sent = Pf.at[idx, idx].set(0.0)
    return kept, sent


def _ef_encode(m, pub, sent, key, spec):
    """Shared public-copy core: message + copy -> (decoded delta c,
    copy'). The transmitted delta is ``c = C(m − pub)`` and sender plus
    receivers advance the copy in lockstep: ``pub' = pub + c``. Clients
    with zero off-diagonal column mass transmit nothing and keep their
    copy unchanged (receivers saw no update). Conservation, exact in f32
    per transmitting client: ``c + (m − pub') == m − pub`` — the owed
    mass is split between this round's wire and the carried residual."""
    sends = (sent.sum(axis=0) > 0)[:, None]
    u = m - pub
    c = jnp.where(sends, encode_decode(u, key, spec), 0.0)
    # explicit where (not pub + 0): keeps silent clients' copies BITWISE
    # untouched (x + 0 flips -0.0 to +0.0)
    pub2 = jnp.where(sends, pub + c, pub)
    return c, pub2


def compressed_pushsum_mix(flat, w, P, pub, key, spec: CompressionSpec):
    """Synchronous exchange with delta-coded transmissions: ``z' =
    (kept·z + sent @ (pub + C(z − pub))) / (P·w)`` — the compressed
    counterpart of :func:`repro.core.gossip.pushsum_mix_debiased`.
    Receivers mix the DENSE updated copies, so sparsification never
    zero-fills a received coordinate and the de-bias stays exact. f32
    accumulation; returns ``(z', w', pub')``. With a lossless codec
    (``pub' ≡ z``) this reduces to the plain ``P @ z`` exchange."""
    f = flat.astype(jnp.float32)
    Pf = jnp.asarray(P, jnp.float32)
    kept, sent = _split_P(Pf)
    c, pub2 = _ef_encode(f, pub, sent, key, spec)
    mixed = kept[:, None] * f + mix_dot(sent, pub2)
    w2 = mix_dot(Pf, w.astype(jnp.float32))
    z2 = mixed / w2[:, None]
    return z2.astype(flat.dtype), w2.astype(w.dtype), pub2


def compressed_stale_mix(flat, w, kept, sent, buf_t0, buf_w0, pub, key,
                         spec: CompressionSpec):
    """Stale (async τ>0) exchange with delta-coded transmissions — the
    compressed counterpart of :func:`repro.core.gossip.stale_mix_apply`:
    the public copy tracks the raw PushSum numerator θ = z·w (the
    quantity that enters the in-flight buffer), ``sent @ (pub + C(θ −
    pub))`` enters the buffer dense, kept mass and deliveries stay
    exact. Returns ``(z', send_t, w', send_w, pub')``; the caller owns
    the buffer rotation. De-bias weights are never compressed, so total
    w-mass (clients + buffer) is conserved exactly at any τ."""
    f = flat.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    theta = f * wf[:, None]
    c, pub2 = _ef_encode(theta, pub, sent, key, spec)
    send_t = mix_dot(sent.astype(jnp.float32), pub2)
    send_w = mix_dot(sent.astype(jnp.float32), wf)
    mixed = kept.astype(jnp.float32)[:, None] * theta \
        + buf_t0.astype(jnp.float32)
    w2 = kept.astype(jnp.float32) * wf + buf_w0.astype(jnp.float32)
    z2 = mixed / w2[:, None]
    return (z2.astype(flat.dtype), send_t.astype(flat.dtype),
            w2.astype(w.dtype), send_w.astype(w.dtype), pub2)


# ---------------------------------------------------------------------------
# numpy oracles + executable spec (what tests/test_compress.py holds the
# jax ops and the engine to)


def topk_reference(u: np.ndarray, ratio: float) -> np.ndarray:
    """Numpy twin of the top-k codec (stable argsort ties == lax.top_k's
    lowest-index-first), bf16 wire rounding via ml_dtypes."""
    import ml_dtypes
    u = np.asarray(u, np.float32)
    k = topk_k(u.shape[1], ratio)
    idx = np.argsort(-np.abs(u), axis=1, kind="stable")[:, :k]
    mask = np.zeros(u.shape, bool)
    np.put_along_axis(mask, idx, True, axis=1)
    wire = u.astype(ml_dtypes.bfloat16).astype(np.float32)
    return np.where(mask, wire, 0.0)


def int8_reference(u: np.ndarray, noise: np.ndarray) -> np.ndarray:
    """Numpy twin of the int8 stochastic-rounding codec for a GIVEN noise
    block (tests feed the same U[0,1) draw to both sides)."""
    u = np.asarray(u, np.float32)
    scale = np.maximum(np.abs(u).max(axis=1), 1e-12).astype(np.float32) / \
        np.float32(127.0)
    x = u / scale[:, None]
    lo = np.floor(x)
    q = lo + (np.asarray(noise, np.float32) < (x - lo))
    q = np.clip(q, -127.0, 127.0).astype(np.float32)
    return q * scale[:, None]


def ef_encode_reference(m, pub, sent, spec: CompressionSpec, noise=None):
    """Numpy twin of the public-copy core: returns ``(c, pub')``.
    The conservation invariant ``c + (m − pub') == m − pub`` (per
    transmitting client, exact) is THE property tests pin."""
    m = np.asarray(m, np.float32)
    pub = np.asarray(pub, np.float32)
    sends = (np.asarray(sent).sum(axis=0) > 0)[:, None]
    u = m - pub
    if spec.mode == "topk":
        c = topk_reference(u, spec.ratio)
    elif spec.mode == "int8":
        c = int8_reference(u, noise)
    else:
        raise ValueError(spec.mode)
    c = np.where(sends, c, 0.0).astype(np.float32)
    pub2 = np.where(sends, pub + c, pub).astype(np.float32)
    return c, pub2


def compressed_gossip_reference(z0, w0, Ps, spec: CompressionSpec,
                                noises=None):
    """Numpy executable spec of the SYNCHRONOUS compressed exchange — the
    round body :func:`compressed_pushsum_mix` implements on device,
    f32 throughout to mirror the jax path bit-closely.

    ``z0``: [K, D] client vectors; ``w0``: [K] de-bias weights; ``Ps``:
    iterable of [K, K] column-stochastic matrices. ``noises``: one
    U[0,1) [K, D] block per round for int8 (None for the deterministic
    top-k). Returns ``(z, w, pub)`` after ``len(Ps)`` rounds (copies
    warm-start at ``z0``, matching the engine's setup broadcast).
    Invariants (tests/test_compress.py): per round and
    per transmitting client ``c + (message − pub') == message − pub``
    exactly; non-transmitting clients keep ``pub`` untouched; receivers
    mix the dense ``pub'``; w evolves exactly as the uncompressed
    protocol (weights are never compressed)."""
    z = np.asarray(z0, np.float32)
    w = np.asarray(w0, np.float32)
    pub = z.copy()
    for t, P in enumerate(Ps):
        Pf = np.asarray(P, np.float32)
        kept = np.diag(Pf).copy()
        sent = Pf.copy()
        np.fill_diagonal(sent, 0.0)
        c, pub = ef_encode_reference(
            z, pub, sent, spec,
            noise=None if noises is None else noises[t])
        mixed = kept[:, None] * z + sent @ pub
        w = Pf @ w
        z = mixed / w[:, None]
    return z, w, pub
