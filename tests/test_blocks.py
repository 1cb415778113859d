"""Round-block execution (``FederationEngine.run_rounds``) engine-level
semantics: stacked [T, K] metric trajectories, block-edge bulk accountant
stepping, checkpoint cadences cut to block edges, the shard_map block, and
batched-vs-sequential cohort evaluation. The end-to-end blocked ==
per-round BIT-IDENTITY assertions (every method × backend × block size,
dropout and DP included) live in the table-driven matrix of
tests/test_conformance.py."""
import dataclasses
import os

import jax
import numpy as np
import pytest

from repro.configs.base import DPConfig, ProxyFLConfig
from repro.core.baselines import run_federated
from repro.core.engine import dml_engine, round_key, single_model_engine
from repro.core.protocol import ModelSpec, evaluate, evaluate_batched
from repro.data.synthetic import make_classification_data
from repro.nn.modules import tree_flatten_vector
from repro.nn.vision import get_vision_model

K, N_CLASSES, SHAPE = 4, 10, (14, 14, 1)


@pytest.fixture(scope="module")
def fed_data():
    key = jax.random.PRNGKey(0)
    x, y = make_classification_data(key, 1200, SHAPE, N_CLASSES, sep=2.0)
    return [(x[i * 300:(i + 1) * 300], y[i * 300:(i + 1) * 300])
            for i in range(K)]


@pytest.fixture(scope="module")
def mlp_spec():
    vm = get_vision_model("mlp")
    return ModelSpec("mlp", lambda k: vm.init(k, SHAPE, N_CLASSES), vm.apply)


def _final_flats(res):
    out = {}
    for role in ("proxy_params", "private_params", "params"):
        if hasattr(res["clients"][0], role):
            out[role] = np.stack([
                np.asarray(tree_flatten_vector(getattr(c, role)))
                for c in res["clients"]])
    return out


# ---------------------------------------------------------------------------
# engine-level semantics


@pytest.mark.fast
def test_hier_blocks_stack_and_compile_once(fed_data, mlp_spec):
    """A multi-block hier run pads/stacks the cohort data exactly ONCE and
    compiles exactly ONE block program: the factored exchange schedule
    (blocks/src/scale) enters as a runtime argument, so every later block
    must hit both the stacked-data cache and the compiled scan — a
    per-block re-stack or re-trace would silently destroy the amortized
    round-block throughput fig_hier claims."""
    cfg = ProxyFLConfig(n_clients=K, rounds=8, batch_size=50, local_steps=1,
                        n_shards=2, staleness=2, dp=DPConfig(enabled=False))
    eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                              backend="hier")
    eng._data_cache.clear()
    eng._stack_misses = 0
    key = jax.random.PRNGKey(0)
    state = eng.init_states(key)
    misses, progs = [], []
    for blk in range(4):
        state, _ = eng.run_rounds(state, fed_data, blk * 2, 2, key)
        misses.append(eng._stack_misses)
        progs.append(len(eng._rounds))
    assert misses == [1, 1, 1, 1], f"per-block stack misses grew: {misses}"
    assert progs == [1, 1, 1, 1], f"per-block program count grew: {progs}"


@pytest.mark.fast
def test_run_rounds_metrics_stacked_per_round(fed_data, mlp_spec):
    """run_rounds returns [n_rounds, K] metric trajectories matching the
    per-round run_round values bit-for-bit (NaN rows for §3.4 dropouts)."""
    cfg = ProxyFLConfig(n_clients=K, rounds=3, batch_size=50, local_steps=2,
                        dropout_rate=0.3, seed=3,
                        dp=DPConfig(enabled=False))
    key = jax.random.PRNGKey(0)
    eng = dml_engine((mlp_spec,) * K, mlp_spec, cfg, backend="vmap")
    state = eng.init_states(key)
    state_b, ms = eng.run_rounds(state, fed_data, 0, 3, key)

    state_r = eng.init_states(key)
    rows = []
    for t in range(3):
        state_r, m = eng.run_round(state_r, fed_data, t, round_key(key, t))
        rows.append(m)
    for k in ms:
        assert ms[k].shape == (3, K)
        np.testing.assert_array_equal(
            ms[k], np.stack([r[k] for r in rows]), err_msg=k)
    np.testing.assert_array_equal(
        np.asarray(jax.vmap(tree_flatten_vector)(state_b["proxy"]["params"])),
        np.asarray(jax.vmap(tree_flatten_vector)(state_r["proxy"]["params"])))


@pytest.mark.fast
def test_run_rounds_bulk_accountant_matches_per_round(fed_data, mlp_spec):
    """Block-edge bulk accountant stepping lands on the same step counters
    (and therefore the same epsilon) as per-round stepping, dropout
    included."""
    from repro.core.accountant import PrivacyAccountant
    cfg = ProxyFLConfig(n_clients=K, rounds=4, batch_size=50, local_steps=2,
                        dropout_rate=0.4, seed=5,
                        dp=DPConfig(enabled=True))
    key = jax.random.PRNGKey(0)
    counts = {}
    for label, drive in (("block", lambda e, s: e.run_rounds(
            s, fed_data, 0, 4, key)[0]),
            ("perround", None)):
        eng = single_model_engine(mlp_spec, cfg, True, mix="pushsum",
                                  backend="vmap")
        eng.attach_accountants(
            [PrivacyAccountant(1.0, 0.1, 1e-5) for _ in range(K)])
        state = eng.init_states(key)
        if drive is not None:
            state = drive(eng, state)
        else:
            for t in range(4):
                state, _ = eng.run_round(state, fed_data, t,
                                         round_key(key, t))
        counts[label] = [a.steps for a in eng.accountants]
    assert counts["block"] == counts["perround"]


@pytest.mark.fast
def test_blocked_checkpoint_cadence_lands_on_block_edges(tmp_path, fed_data,
                                                         mlp_spec):
    """checkpoint_every=2 with rounds_per_block=4: blocks are CUT at the
    cadence rounds, so the snapshot set equals the per-round loop's, and a
    kill-after-block resume replays bit-identically."""
    from repro.checkpoint.federation import FederationCheckpointer
    cfg = ProxyFLConfig(n_clients=K, rounds=5, batch_size=50, local_steps=1,
                        dp=DPConfig(enabled=False))
    d = os.path.join(str(tmp_path), "ck")
    run = lambda c, **kw: run_federated(
        "proxyfl", [mlp_spec] * K, mlp_spec, fed_data, fed_data[0], c,
        seed=0, eval_every=c.rounds, backend="vmap", rounds_per_block=4,
        checkpoint_dir=d, checkpoint_every=2, **kw)
    ref = run(cfg)
    saved = FederationCheckpointer(
        os.path.join(d, "proxyfl_s0")).saved_rounds()
    assert saved == [2, 4]  # exactly the per-round cadence
    # kill after the first block-edge snapshot, resume, finish
    killed = run(dataclasses.replace(cfg, rounds=2))
    resumed = run(cfg, resume=True)
    for role, v in _final_flats(resumed).items():
        np.testing.assert_array_equal(_final_flats(ref)[role], v,
                                      err_msg=role)
    assert resumed["history"][-1]["round"] == cfg.rounds


def test_run_rounds_shard_map_block_bit_identical(fed_data, mlp_spec):
    """The shard_map block (per-round collective schedules unrolled inside
    one jit) replays run_round bit-exactly — 1-device mesh smoke; the K=4
    equivalence runs in the forced four-device child process of
    test_engine."""
    from repro.core.engine import FederationEngine
    mesh = jax.make_mesh((1,), ("clients",))
    cfg = ProxyFLConfig(n_clients=1, rounds=2, batch_size=50, local_steps=2,
                        dp=DPConfig(enabled=False))
    vmap_eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                                   backend="vmap")
    key = jax.random.PRNGKey(0)
    finals = {}
    for label in ("block", "perround"):
        eng = FederationEngine(
            cfg, n_clients=1, step_fns=vmap_eng.step_fns[0],
            init_fns=vmap_eng.init_fns[0], sample_fn=vmap_eng.sample_fn,
            backend="shard_map", mix="pushsum", mesh=mesh, axis="clients")
        state = eng.init_states(key)
        if label == "block":
            state, ms = eng.run_rounds(state, fed_data[:1], 0, 2, key)
            assert ms["loss"].shape == (2, 1)
        else:
            for t in range(2):
                state, _ = eng.run_round(state, fed_data[:1], t,
                                         round_key(key, t))
        finals[label] = np.asarray(
            jax.vmap(tree_flatten_vector)(state["proxy"]["params"]))
    np.testing.assert_array_equal(finals["block"], finals["perround"])


# ---------------------------------------------------------------------------
# batched evaluation


@pytest.mark.fast
def test_evaluate_batched_matches_sequential(fed_data, mlp_spec):
    x, y = fed_data[0]
    params = [mlp_spec.init(jax.random.PRNGKey(s)) for s in range(3)]
    stacked = jax.tree_util.tree_map(lambda *xs: jax.numpy.stack(xs), *params)
    batched = evaluate_batched(mlp_spec, stacked, x, y, batch=128)
    seq = [evaluate(mlp_spec, p, x, y, batch=128) for p in params]
    np.testing.assert_allclose(batched, seq, atol=1e-12)
