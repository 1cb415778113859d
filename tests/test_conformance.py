"""Cross-backend conformance matrix: ONE table-driven suite asserting that
every execution backend of the FederationEngine — loop, vmap, shard_map
(1-device), async-τ0/τ>0 and the two-level hier backend — agrees across
methods, §3.4 dropout, ragged cohorts and round-block sizes. This file replaces the ad-hoc
pairwise equivalence tests previously scattered across test_engine.py,
test_blocks.py and test_ragged.py.

Two agreement grades, stated per case:

``exact``
    Params AND epsilon bit-identical (``np.testing.assert_array_equal``).
    Holds whenever the two runs execute the SAME compiled program with the
    same inputs: vmap vs async-τ0 (the τ=0 async backend runs the vmap
    round program verbatim), any round-block size vs per-round on one
    backend (blocks only remove host synchronization), and async-τ>0
    blocked vs per-round (the stale core is shared, the buffer rides in
    the scan carry).

``close``
    ``np.testing.assert_allclose(atol=1e-5, rtol=1e-4)``. Documented
    float divergence: the loop backend jits each client's step separately
    while the stacked backends run one vmapped scan — same math, different
    op order/fusion. Epsilon is still compared exactly (the accountant is
    host-side and identical).

Epsilon is part of EVERY comparison: the DP accountant step schedule is a
backend invariant (staleness delays gossip delivery, never local compute,
so sample rates and step counts cannot change — asserted explicitly by
the ``async-t2-epsilon-matches-sync`` case).

The ``pallas-*`` cases pin the Pallas-fused round hot path
(``ProxyFLConfig.use_pallas`` — fused gossip mix + DP clip→noise→step,
interpret mode on CPU): fused vs plain is ``close`` on loop, vmap and
async-τ2 (f32 kernel accumulation — same math, different reduction
order), epsilon stays EXACT (the accountant is host-side and the fused
path never changes step counts), and fused round-blocks stay bit-identical
to fused per-round execution. A run tuple may carry a third element —
``(backend, rounds_per_block, use_pallas)`` — to fuse one side only.

The ``compress-*`` cases pin the compressed proxy exchange
(``ProxyFLConfig.compress`` — top-k / int8 wire formats with error
feedback, repro.core.compress): ``compress="none"`` requested explicitly
through the run_federated override is bit-identical to the default
uncompressed protocol on loop, vmap, async-τ0 AND async-τ2 (the engine
must bypass the compression wrapper entirely, not merely approximate it),
compressed round-blocks of any size are bit-identical to compressed
per-round execution (the error-feedback residual rides the scan carry),
and topk/int8 agree loop-vs-vmap under the ``quantized`` grade below. A
run tuple may carry a FOURTH element — ``(backend, rounds_per_block,
use_pallas, compress)`` — to compress one side only.

``quantized``
    ``np.testing.assert_allclose(atol=5e-2, rtol=0)``, epsilon still
    EXACT (compression never touches the accountant — it gossips, it does
    not train). Used for topk/int8 loop-vs-vmap: the backends' ~1e-6
    float divergence can flip a top-k selection or an int8 rounding
    decision, so agreement is bounded by the quantization granularity,
    not by fp epsilon.

The ``hier-*`` cases pin the two-level [``n_shards`` × clients-per-shard]
backend: ``n_shards=1`` runs the vmap round programs VERBATIM (the
bit-identity anchor the acceptance bar names), ``n_shards>1`` executes
the SAME flat ``mix_schedule`` matrices factored by edge locality
(block-diagonal intra-shard matmul + at-most-one cross-shard edge per
client) — still ``exact`` at τ=0 because the zero cross-block entries the
dense matmul sums contribute exactly 0.0, hier τ>0 round-blocks are
bit-identical to per-round (the cross-shard in-flight buffer rides the
scan carry), epsilon is τ- and shard-invariant, and ``compress="none"``
stays bitwise (n_shards>1 refuses real codecs at construction).

The ``fast``-marked subset is the CI smoke (scripts/ci.sh --fast): it
covers loop==vmap, ragged-on-vmap, block bit-identity, the async-τ0
equivalence smoke, async-τ2 block/resume bit-identity and the compression
parity slice (none-bitwise + topk/int8 loop-vs-vmap) without exceeding
the shard budget.
"""
import dataclasses
import os
from dataclasses import dataclass, field
from typing import Tuple

import jax
import numpy as np
import pytest

from repro.configs.base import DPConfig, ProxyFLConfig
from repro.core.baselines import METHODS, run_federated
from repro.core.engine import (FederationEngine, dml_engine, round_key,
                               single_model_engine)
from repro.core.protocol import ModelSpec
from repro.data.partition import partition_dirichlet
from repro.data.synthetic import make_classification_data
from repro.nn.modules import tree_flatten_vector
from repro.nn.vision import get_vision_model

K, N_CLASSES, SHAPE = 4, 10, (14, 14, 1)


@pytest.fixture(scope="module")
def mlp_spec():
    vm = get_vision_model("mlp")
    return ModelSpec("mlp", lambda k: vm.init(k, SHAPE, N_CLASSES), vm.apply)


@pytest.fixture(scope="module")
def datasets():
    key = jax.random.PRNGKey(0)
    x, y = make_classification_data(key, 1200, SHAPE, N_CLASSES, sep=2.0)
    rect = [(x[i * 300:(i + 1) * 300], y[i * 300:(i + 1) * 300])
            for i in range(K)]
    idxs = partition_dirichlet(np.random.default_rng(0), np.asarray(y), K,
                               0.5)
    ragged = [(x[i], y[i]) for i in idxs]
    assert len({d[0].shape[0] for d in ragged}) > 1, "fixture must be ragged"
    return {"rect": rect, "ragged": ragged}


@pytest.fixture(scope="module")
def run_cache():
    """Memo of completed runs: references (e.g. the vmap B=1 trajectory)
    are shared across every case that compares against them."""
    return {}


# ---------------------------------------------------------------------------
# the matrix


@dataclass(frozen=True)
class Case:
    id: str
    # (backend, rounds_per_block[, use_pallas[, compress]]) of the
    # reference and each candidate run; backend None = run_federated's
    # default ("auto"), the optional third element fuses that run's hot
    # path (default False), the optional fourth sets that run's compress
    # mode override (default None = leave cfg.compress alone)
    ref: Tuple
    cands: Tuple
    expect: str = "exact"   # "exact" | "close" | "epsilon" | "quantized"
    method: str = "proxyfl"
    data: str = "rect"             # "rect" | "ragged"
    fast: bool = False
    cfg: Tuple = field(default=())  # ProxyFLConfig overrides, sorted items


def _c(id, ref, cands, **kw):
    cfg = {k: kw.pop(k) for k in list(kw)
           if k in ("rounds", "local_steps", "dropout_rate", "staleness",
                    "dp", "seed", "use_pallas", "compress",
                    "compress_ratio", "n_shards")}
    return Case(id=id, ref=ref, cands=tuple(cands),
                cfg=tuple(sorted(cfg.items())), **kw)


CASES = [
    # -- loop vs stacked: documented-allclose ------------------------------
    _c("dml-loop-vs-vmap", ("loop", 1), [("vmap", 1)], expect="close",
       fast=True, rounds=2, local_steps=3, dp=True),
    _c("fml-loop-vs-vmap", ("loop", 1), [("vmap", 1)], expect="close",
       method="fml", rounds=2, local_steps=2),
    _c("fedavg-loop-vs-vmap", ("loop", 1), [("vmap", 1)], expect="close",
       method="fedavg", rounds=1, local_steps=2),
    _c("avgpush-loop-vs-vmap", ("loop", 1), [("vmap", 1)], expect="close",
       method="avgpush", rounds=1, local_steps=2),
    _c("cwt-loop-vs-vmap", ("loop", 1), [("vmap", 1)], expect="close",
       method="cwt", rounds=1, local_steps=2),
    _c("regular-loop-vs-vmap", ("loop", 1), [("vmap", 1)], expect="close",
       method="regular", rounds=1, local_steps=2),
    # -- ragged cohorts (epoch mode: padding + masked sampling + per-client
    #    step masks all in play) ------------------------------------------
    _c("ragged-epoch-loop-vs-vmap", ("loop", 1), [("vmap", 1)],
       expect="close", fast=True, data="ragged", rounds=2, local_steps=0,
       dp=True),
    _c("ragged-dropout-auto-vs-loop", ("loop", 1), [(None, 1)],
       expect="close", data="ragged", rounds=2, local_steps=0,
       dropout_rate=0.3, seed=1),
    # -- round-blocks: any block size is bit-identical per backend ---------
    _c("dml-blocks-bitwise", ("vmap", 1), [("vmap", 2), ("vmap", 4)],
       fast=True, rounds=4, local_steps=2, dp=True, dropout_rate=0.25),
    _c("dml-blocks-bitwise-loop", ("loop", 1), [("loop", 2), ("loop", 4)],
       rounds=4, local_steps=2, dp=True, dropout_rate=0.25),
    _c("fedavg-blocks-bitwise", ("vmap", 1), [("vmap", 3)], fast=True,
       method="fedavg", rounds=3, local_steps=1),
    _c("avgpush-blocks-bitwise", ("vmap", 1), [("vmap", 3)],
       method="avgpush", rounds=3, local_steps=1),
    _c("cwt-blocks-bitwise", ("vmap", 1), [("vmap", 3)], fast=True,
       method="cwt", rounds=3, local_steps=1),
    _c("regular-blocks-bitwise", ("vmap", 1), [("vmap", 3)],
       method="regular", rounds=3, local_steps=1),
    _c("joint-blocks-bitwise", (None, 1), [(None, 2)], method="joint",
       rounds=2, local_steps=1),
    _c("ragged-blocks-bitwise", ("vmap", 1), [("vmap", 2)], data="ragged",
       rounds=2, local_steps=0, dp=True),
    # -- async τ=0 == vmap, bit for bit (the acceptance bar) ---------------
    _c("async-t0-vs-vmap", ("vmap", 1), [("async", 1), ("async", 3)],
       fast=True, rounds=3, local_steps=2, dp=True, dropout_rate=0.25),
    _c("async-t0-fml", ("vmap", 1), [("async", 1)], method="fml",
       rounds=2, local_steps=2),
    _c("async-t0-avgpush", ("vmap", 1), [("async", 1)], method="avgpush",
       rounds=2, local_steps=1),
    _c("async-t0-cwt", ("vmap", 1), [("async", 1)], method="cwt",
       rounds=2, local_steps=1),
    _c("async-t0-ragged", ("vmap", 1), [("async", 1), ("async", 2)],
       data="ragged", rounds=2, local_steps=0, dp=True),
    # -- async τ>0: blocked == per-round, bit for bit; epsilon is
    #    τ-invariant (the DP schedule only sees local compute) ------------
    _c("async-t2-blocks-bitwise", ("async", 1), [("async", 2), ("async", 4)],
       fast=True, rounds=4, local_steps=2, dp=True, dropout_rate=0.25,
       staleness=2),
    _c("async-t1-blocks-bitwise", ("async", 1), [("async", 3)],
       rounds=3, local_steps=0, staleness=1, data="ragged"),
    _c("async-t2-epsilon-matches-sync", ("vmap", 1), [("async", 1)],
       expect="epsilon", fast=True, rounds=3, local_steps=2, dp=True,
       dropout_rate=0.25, staleness=2),
    # -- Pallas-fused hot path vs plain XLA: allclose on every matmul-mix
    #    backend, epsilon exact (accountant untouched by fusion) ----------
    _c("pallas-vmap-vs-plain", ("vmap", 1), [("vmap", 1, True)],
       expect="close", fast=True, rounds=2, local_steps=2, dp=True),
    _c("pallas-loop-vs-plain", ("loop", 1), [("loop", 1, True)],
       expect="close", rounds=2, local_steps=2, dp=True),
    _c("pallas-async-t2-vs-plain", ("async", 1), [("async", 1, True)],
       expect="close", rounds=3, local_steps=2, dp=True, staleness=2),
    _c("pallas-ragged-vs-plain", ("vmap", 1), [("vmap", 1, True)],
       expect="close", data="ragged", rounds=2, local_steps=0, dp=True),
    # fused round-blocks == fused per-round, bit for bit (same program)
    _c("pallas-blocks-bitwise", ("vmap", 1), [("vmap", 2), ("vmap", 4)],
       fast=True, rounds=4, local_steps=2, dp=True, use_pallas=True),
    # -- compressed exchange: compress="none" requested explicitly is the
    #    uncompressed protocol VERBATIM on every backend (the engine must
    #    bypass the compression wrapper, not approximate it) -------------
    _c("compress-none-bitwise-sync",
       ("vmap", 1), [("vmap", 1, False, "none"),
                     ("async", 1, False, "none")],
       fast=True, rounds=2, local_steps=2, dp=True),
    _c("compress-none-bitwise-loop", ("loop", 1),
       [("loop", 1, False, "none")], fast=True, rounds=2, local_steps=2,
       dp=True),
    _c("compress-none-bitwise-async-t2", ("async", 1),
       [("async", 1, False, "none")], rounds=3, local_steps=2, dp=True,
       staleness=2),
    # topk/int8 loop vs vmap: agreement bounded by the quantization
    # granularity (a 1e-6 training divergence can flip a selection), with
    # epsilon compared EXACTLY — compression must never touch the
    # accountant
    _c("compress-topk-loop-vs-vmap", ("loop", 1), [("vmap", 1)],
       expect="quantized", fast=True, rounds=2, local_steps=2, dp=True,
       compress="topk"),
    _c("compress-int8-loop-vs-vmap", ("loop", 1), [("vmap", 1)],
       expect="quantized", fast=True, rounds=2, local_steps=2, dp=True,
       compress="int8"),
    # compressed round-blocks == compressed per-round, bit for bit (the
    # error-feedback residual rides the scan carry)
    _c("compress-topk-blocks-bitwise", ("vmap", 1), [("vmap", 2),
                                                     ("vmap", 4)],
       rounds=4, local_steps=2, dp=True, compress="topk",
       compress_ratio=0.1),
    _c("compress-int8-async-t2-blocks-bitwise", ("async", 1),
       [("async", 2), ("async", 4)], rounds=4, local_steps=2, dp=True,
       staleness=2, dropout_rate=0.25, compress="int8"),
    _c("compress-topk-ragged", ("vmap", 1), [("vmap", 2)], data="ragged",
       rounds=2, local_steps=0, dp=True, compress="topk"),
    # -- hier two-level backend: n_shards=1 IS the vmap program (bitwise
    #    anchor); n_shards>1 factors the SAME flat P^(t) block-diagonally
    #    and stays exact at τ=0; τ>0 blocked == per-round with the
    #    cross-shard buffer in the scan carry; epsilon τ/shard-invariant -
    _c("hier-s1-vs-vmap", ("vmap", 1), [("hier", 1), ("hier", 3)],
       fast=True, rounds=3, local_steps=2, dp=True, n_shards=1),
    _c("hier-t0-s2-vs-vmap", ("vmap", 1), [("hier", 1), ("hier", 2)],
       fast=True, rounds=4, local_steps=2, dp=True, dropout_rate=0.25,
       n_shards=2),
    _c("hier-vs-loop", ("loop", 1), [("hier", 1)], expect="close",
       rounds=2, local_steps=2, dp=True, n_shards=2),
    _c("hier-t0-ragged", ("vmap", 1), [("hier", 1), ("hier", 2)],
       data="ragged", rounds=2, local_steps=0, dp=True, n_shards=2),
    _c("hier-t2-blocks-bitwise", ("hier", 1), [("hier", 2), ("hier", 4)],
       fast=True, rounds=4, local_steps=2, dp=True, dropout_rate=0.25,
       staleness=2, n_shards=2),
    _c("hier-t2-epsilon-matches-sync", ("vmap", 1), [("hier", 1)],
       expect="epsilon", rounds=3, local_steps=2, dp=True, staleness=2,
       n_shards=2),
    _c("hier-compress-none-bitwise", ("hier", 1),
       [("hier", 1, False, "none")], rounds=2, local_steps=2, dp=True,
       n_shards=2),
]


def _mk_cfg(case: Case) -> ProxyFLConfig:
    kw = dict(case.cfg)
    dp = kw.pop("dp", False)
    return ProxyFLConfig(
        n_clients=K, batch_size=50,
        dp=DPConfig(enabled=dp, noise_multiplier=1.0, clip_norm=1.0), **kw)


def _final_flats(res):
    out = {}
    for role in ("proxy_params", "private_params", "params"):
        if hasattr(res["clients"][0], role):
            out[role] = np.stack([
                np.asarray(tree_flatten_vector(getattr(c, role)))
                for c in res["clients"]])
    return out


def _run(cache, case: Case, mlp_spec, datasets, backend, rpb,
         pallas=False, comp=None):
    memo_key = (case.method, case.data, case.cfg, backend, rpb, pallas,
                comp)
    if memo_key in cache:
        return cache[memo_key]
    cfg = _mk_cfg(case)
    data = datasets[case.data]
    res = run_federated(case.method, [mlp_spec] * K, mlp_spec, data,
                        data[0], cfg, seed=0, eval_every=cfg.rounds,
                        backend=backend, rounds_per_block=rpb,
                        use_pallas=pallas or None, compress=comp)
    out = {"flats": _final_flats(res),
           "epsilon": tuple(res["epsilon"]),
           "hist_rounds": tuple(r["round"] for r in res["history"])}
    cache[memo_key] = out
    return out


def _case_params():
    return [pytest.param(c, id=c.id,
                         marks=(pytest.mark.fast,) if c.fast else ())
            for c in CASES]


@pytest.mark.parametrize("case", _case_params())
def test_conformance(case, run_cache, mlp_spec, datasets):
    ref = _run(run_cache, case, mlp_spec, datasets, *case.ref)
    for cand in case.cands:
        backend, rpb, pallas, comp = (tuple(cand) + (False, None))[:4]
        got = _run(run_cache, case, mlp_spec, datasets, backend, rpb,
                   pallas, comp)
        label = (f"{case.id}: {case.ref} vs ({backend}, B={rpb}"
                 f"{', pallas' if pallas else ''}"
                 f"{f', compress={comp}' if comp else ''})")
        assert got["epsilon"] == ref["epsilon"], f"{label}: epsilon differs"
        if case.expect == "epsilon":
            continue
        assert got["hist_rounds"] == ref["hist_rounds"], label
        assert set(got["flats"]) == set(ref["flats"]), label
        for role, v in got["flats"].items():
            if case.expect == "exact":
                np.testing.assert_array_equal(
                    ref["flats"][role], v,
                    err_msg=f"{label}: {role} not bit-identical")
            elif case.expect == "quantized":
                np.testing.assert_allclose(
                    ref["flats"][role], v, atol=5e-2, rtol=0,
                    err_msg=f"{label}: {role} outside quantization bound")
            else:
                np.testing.assert_allclose(
                    ref["flats"][role], v, atol=1e-5, rtol=1e-4,
                    err_msg=f"{label}: {role} outside tolerance")


def test_conformance_table_sanity():
    """Every advertised backend AND every METHODS-table entry appears in
    the matrix, and ids are unique — a silently dropped column (or a new
    method added without a conformance row) would hollow the suite out."""
    ids = [c.id for c in CASES]
    assert len(ids) == len(set(ids))
    backends = {run[0] for c in CASES for run in (c.ref,) + c.cands}
    assert {"loop", "vmap", "async", "hier", None} <= backends
    missing = set(METHODS) - {c.method for c in CASES}
    assert not missing, f"METHODS without a conformance case: {missing}"
    assert any(dict(c.cfg).get("staleness") for c in CASES)
    assert any(c.data == "ragged" for c in CASES)
    assert any(c.fast for c in CASES)
    # the fused hot path must keep a column per matmul-mix backend, plus
    # one fused-vs-fused block bit-identity case
    fused_backends = {run[0] for c in CASES for run in (c.ref,) + c.cands
                      if len(run) > 2 and run[2]}
    assert {"loop", "vmap", "async"} <= fused_backends
    assert any(dict(c.cfg).get("use_pallas") for c in CASES)
    # the compressed exchange must keep: a none-bitwise column on every
    # matmul-mix backend (incl. async-τ2), a quantized loop-vs-vmap column
    # per codec, and a compressed block bit-identity case per scan carry
    none_backends = {run[0] for c in CASES for run in (c.ref,) + c.cands
                     if len(run) > 3 and run[3] == "none"}
    assert {"loop", "vmap", "async"} <= none_backends
    assert any(dict(c.cfg).get("compress") == "none"
               or (len(r) > 3 and r[3] == "none")
               for c in CASES for r in (c.ref,) + c.cands
               if dict(c.cfg).get("staleness"))
    comp_modes = {dict(c.cfg).get("compress") for c in CASES}
    assert {"topk", "int8"} <= comp_modes
    assert any(dict(c.cfg).get("compress") and c.expect == "exact"
               and any(r[1] > 1 for r in c.cands) for c in CASES)
    assert any(dict(c.cfg).get("compress") and dict(c.cfg).get("staleness")
               for c in CASES)
    # the hier two-level backend must keep: the n_shards=1 vmap-verbatim
    # anchor, an n_shards>1 EXACT column, a τ>0 block bit-identity case,
    # a ragged column and a compress-none bitwise column
    hier_cases = [c for c in CASES
                  if any(r[0] == "hier" for r in (c.ref,) + c.cands)]
    assert any(dict(c.cfg).get("n_shards") == 1 and c.expect == "exact"
               for c in hier_cases)
    assert any(dict(c.cfg).get("n_shards", 1) > 1 and c.expect == "exact"
               for c in hier_cases)
    assert any(dict(c.cfg).get("staleness")
               and any(r[1] > 1 for r in c.cands) for c in hier_cases)
    assert any(c.data == "ragged" for c in hier_cases)
    assert any(len(r) > 3 and r[3] == "none"
               for c in hier_cases for r in (c.ref,) + c.cands)


@pytest.mark.fast
def test_round_metrics_agree_across_backends(datasets, mlp_spec):
    """Per-round TRAINING metrics (loss trajectories), not just final
    params: async-τ0 must reproduce vmap's metrics bit-for-bit and the
    loop backend must agree within tolerance — on a ragged epoch-mode
    cohort, so padding/step-mask metric gathering is in play too."""
    cfg = ProxyFLConfig(n_clients=K, rounds=2, batch_size=50, local_steps=0,
                        dp=DPConfig(enabled=True))
    key = jax.random.PRNGKey(0)
    results = {}
    for backend in ("loop", "vmap", "async"):
        eng = dml_engine((mlp_spec,) * K, mlp_spec, cfg, backend=backend)
        state = eng.init_states(key)
        state, metrics = eng.run_rounds(state, datasets["ragged"], 0,
                                        cfg.rounds, key)
        results[backend] = metrics
    assert set(results["loop"]) == set(results["vmap"]) \
        == set(results["async"])
    for k in results["vmap"]:
        assert results["vmap"][k].shape == (cfg.rounds, K)
        np.testing.assert_array_equal(results["vmap"][k],
                                      results["async"][k], err_msg=k)
        np.testing.assert_allclose(results["loop"][k], results["vmap"][k],
                                   atol=1e-4, rtol=1e-3, err_msg=k)


# ---------------------------------------------------------------------------
# shard_map column: run_federated cannot construct a mesh, so the 1-device
# conformance runs at engine level (the K=4 collective equivalence runs in
# the forced four-device child process of test_engine)


def test_shard_map_k1_matches_vmap_bitwise(datasets, mlp_spec):
    cfg = ProxyFLConfig(n_clients=1, rounds=3, batch_size=50, local_steps=2,
                        dp=DPConfig(enabled=False))
    mesh = jax.make_mesh((1,), ("clients",))
    vmap_eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                                   backend="vmap", n_clients=1)
    key = jax.random.PRNGKey(0)
    data = datasets["rect"][:1]
    finals = {}
    for label in ("vmap", "shard_map"):
        if label == "vmap":
            eng = vmap_eng
        else:
            eng = FederationEngine(
                cfg, n_clients=1, step_fns=vmap_eng.step_fns[0],
                init_fns=vmap_eng.init_fns[0],
                sample_fn=vmap_eng.sample_fn, backend="shard_map",
                mix="pushsum", mesh=mesh, axis="clients")
        state = eng.init_states(key)
        state, _ = eng.run_rounds(state, data, 0, cfg.rounds, key)
        finals[label] = np.asarray(
            jax.vmap(tree_flatten_vector)(state["proxy"]["params"]))
    np.testing.assert_array_equal(finals["vmap"], finals["shard_map"])


def test_hier_engine_k8_s4_matches_vmap_bitwise(mlp_spec):
    """K=8, S=4 at engine level: the exponential shift classes {1, 2, 4}
    exercise every (q, r) = divmod(shift, L) split of the factored
    cross-shard edge — pure cross-permutation (q odd, r=0), intra-only
    (shift < L) and the mixed case — so the blockdiag+scatter execution
    must reproduce the dense vmap matmul bit-for-bit on all of them."""
    cfg = ProxyFLConfig(n_clients=8, rounds=3, batch_size=50, local_steps=1,
                        n_shards=4, dp=DPConfig(enabled=False))
    key = jax.random.PRNGKey(0)
    x, y = make_classification_data(key, 400, SHAPE, N_CLASSES, sep=2.0)
    data = [(x[i * 50:(i + 1) * 50], y[i * 50:(i + 1) * 50])
            for i in range(8)]
    finals = {}
    for backend in ("vmap", "hier"):
        eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                                  backend=backend, n_clients=8)
        state = eng.init_states(key)
        state, _ = eng.run_rounds(state, data, 0, cfg.rounds, key)
        finals[backend] = np.asarray(
            jax.vmap(tree_flatten_vector)(state["proxy"]["params"]))
    np.testing.assert_array_equal(finals["vmap"], finals["hier"])


# ---------------------------------------------------------------------------
# commitment-chain conformance (verifiable federation)


def test_commitment_chain_backend_invariant_at_tau0(tmp_path, datasets,
                                                    mlp_spec):
    """loop, vmap and hier (S=2) snapshots of the same federation must
    produce the IDENTICAL audit trail — same per-leaf digests, same client
    commitments, same hash chain — since commitments are computed from the
    backend-portable canonical payload. lr=0 isolates the exchange: with
    local steps active the loop and stacked backends agree only to ~1e-8
    (XLA fuses the per-step chain differently — the documented-allclose
    rows of CASES), which sha256 cannot absorb; mix-only dynamics are
    bitwise across all three backends, so chain equality here pins the
    commitment layer's backend invariance without conflating it with
    float-fusion divergence."""
    import json

    cfg = ProxyFLConfig(n_clients=K, rounds=2, batch_size=50, local_steps=1,
                        lr=0.0, dp=DPConfig(enabled=True))
    chains = {}
    for backend, shards in (("loop", 1), ("vmap", 1), ("hier", 2)):
        d = os.path.join(str(tmp_path), backend)
        run_federated("proxyfl", [mlp_spec] * K, mlp_spec, datasets["rect"],
                      datasets["rect"][0], cfg, seed=0, eval_every=cfg.rounds,
                      backend=backend, n_shards=shards,
                      checkpoint_dir=d, checkpoint_every=1)
        with open(os.path.join(d, "proxyfl_s0", "audit.jsonl")) as f:
            chains[backend] = [json.loads(line) for line in f]
    assert [e["rounds_done"] for e in chains["vmap"]] == [1, 2]
    assert chains["loop"] == chains["vmap"] == chains["hier"]


# ---------------------------------------------------------------------------
# async invariants beyond pairwise agreement


@pytest.mark.fast
def test_async_stale_mass_conserved_engine_level(datasets, mlp_spec):
    """τ=2 with §3.4 dropout, lr=0 to isolate the exchange: total raw
    PushSum mass Σ z·w and total de-bias weight — clients PLUS the
    in-flight buffer — are conserved every round (the engine-level twin of
    the ``stale_gossip_reference`` property tests)."""
    cfg = ProxyFLConfig(n_clients=K, rounds=4, batch_size=50, local_steps=1,
                        lr=0.0, staleness=2, dp=DPConfig(enabled=False))
    eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                              backend="async")
    key = jax.random.PRNGKey(0)
    state = eng.init_states(key)

    def masses(st):
        z = np.asarray(jax.vmap(tree_flatten_vector)(
            st["clients"]["proxy"]["params"]))
        w = np.asarray(st["clients"]["w"])
        return ((z * w[:, None]).sum() + np.asarray(st["stale_theta"]).sum(),
                w.sum() + np.asarray(st["stale_w"]).sum())

    theta0, w0 = masses(state)
    assert w0 == K  # buffer starts empty, weights at 1
    masks = [np.array([True, False, True, True]),
             np.array([False, True, False, True]),
             None,
             np.array([True, True, False, False])]
    for t, act in enumerate(masks):
        state, _ = eng.run_round(state, datasets["rect"], t,
                                 round_key(key, t), active=act)
        theta_m, w_m = masses(state)
        np.testing.assert_allclose(theta_m, theta0, rtol=1e-5)
        np.testing.assert_allclose(w_m, K, rtol=1e-6)


@pytest.mark.fast
def test_async_t2_kill_resume_bit_identical(tmp_path, datasets, mlp_spec):
    """Kill an async-τ2 federation on a block edge and resume: with τ=2
    the post-resume rounds consume deliveries recorded BEFORE the kill, so
    this passes only if the in-flight buffer round-trips through the
    checkpoint bit-exactly."""
    cfg = ProxyFLConfig(n_clients=K, rounds=4, batch_size=50, local_steps=2,
                        staleness=2, dropout_rate=0.25,
                        dp=DPConfig(enabled=True, noise_multiplier=1.0,
                                    clip_norm=1.0))
    d = os.path.join(str(tmp_path), "ck")
    run = lambda c, **kw: run_federated(
        "proxyfl", [mlp_spec] * K, mlp_spec, datasets["rect"],
        datasets["rect"][0], c, seed=0, eval_every=c.rounds,
        backend="async", rounds_per_block=2, **kw)
    ref = run(cfg)  # uninterrupted, no checkpointing
    ckpt = dict(checkpoint_dir=d, checkpoint_every=2)
    run(dataclasses.replace(cfg, rounds=2), **ckpt)  # "killed" after block 1
    resumed = run(cfg, resume=True, **ckpt)
    for role, v in _final_flats(resumed).items():
        np.testing.assert_array_equal(_final_flats(ref)[role], v,
                                      err_msg=role)
    assert resumed["epsilon"] == ref["epsilon"]


def test_async_staleness_rejects_ring_mix(mlp_spec):
    """CWT's pure-permutation ring keeps no self mass: a delayed delivery
    would leave clients model-less for τ rounds — refused at construction,
    not surfaced as NaNs mid-run."""
    cfg = ProxyFLConfig(n_clients=K, rounds=1, batch_size=50, local_steps=1,
                        staleness=1, dp=DPConfig(enabled=False))
    with pytest.raises(ValueError, match="ring"):
        single_model_engine(mlp_spec, cfg, False, mix="ring",
                            backend="async")
