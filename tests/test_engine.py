"""FederationEngine semantics: §3.4 dropout/join (inactive clients frozen,
PushSum mass conserved under time-varying membership), the unified mixing
matrices behind every METHODS-table aggregation rule, checkpoint round-
trips, and backend construction rules. Cross-backend EQUIVALENCE (loop ==
vmap == async-τ0, blocked == per-round, ...) lives in the table-driven
matrix of tests/test_conformance.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import DPConfig, ProxyFLConfig
from repro.core.engine import (FederationEngine, active_mask, dml_engine,
                               single_model_engine)
from repro.core.gossip import mix_matrix, pushsum_mix
from repro.core.protocol import ModelSpec
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


def _flat_clients(states):
    if isinstance(states, list):  # loop backend
        return np.stack([np.asarray(tree_flatten_vector(s["proxy"]["params"]))
                         for s in states])
    return np.asarray(jax.vmap(tree_flatten_vector)(states["proxy"]["params"]))


def _flat_private(states):
    if isinstance(states, list):
        return np.stack([np.asarray(tree_flatten_vector(s["private"]["params"]))
                         for s in states])
    return np.asarray(
        jax.vmap(tree_flatten_vector)(states["private"]["params"]))


# ---------------------------------------------------------------------------
# dropout / join (§3.4)


@pytest.mark.fast
@pytest.mark.parametrize("backend", ("loop", "vmap"))
def test_dropout_mass_conservation(fed_data, mlp_spec, backend):
    """With clients dropping in/out every round, PushSum stays column-
    stochastic on the full cohort: total parameter mass and total w are
    conserved, and an inactive client's state is untouched that round.
    lr=0 isolates the gossip dynamics from local training."""
    cfg = ProxyFLConfig(n_clients=K, rounds=4, batch_size=50, local_steps=1,
                        lr=0.0, dp=DPConfig(enabled=False))
    key = jax.random.PRNGKey(0)
    eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                              backend=backend)
    state = eng.init_states(key)
    mass0 = _flat_clients(state).sum()
    masks = [np.array([True, False, True, True]),
             np.array([False, True, False, True]),
             None,
             np.array([True, True, False, False])]
    for t, act in enumerate(masks):
        before = _flat_clients(state)
        state, _ = eng.run_round(state, fed_data, t,
                                 jax.random.fold_in(key, t), active=act)
        after = _flat_clients(state)
        w = np.asarray([np.asarray(s["w"]) for s in eng.export_states(state)])
        np.testing.assert_allclose(after.sum(), mass0, rtol=1e-5)
        np.testing.assert_allclose(w.sum(), K, rtol=1e-6)
        if act is not None:
            for k in np.where(~act)[0]:
                np.testing.assert_array_equal(before[k], after[k])


def test_dropout_schedule_deterministic():
    cfg = ProxyFLConfig(n_clients=8, dropout_rate=0.5, seed=11)
    a = [active_mask(t, 8, cfg) for t in range(5)]
    b = [active_mask(t, 8, cfg) for t in range(5)]
    for ma, mb in zip(a, b):
        np.testing.assert_array_equal(ma, mb)
        assert ma.sum() >= 1  # min_active floor
    assert any((m != a[0]).any() for m in a[1:])  # time-varying
    assert active_mask(0, 8, ProxyFLConfig(n_clients=8)) is None


@pytest.mark.fast
def test_mix_matrices_column_stochastic_with_active():
    act = np.array([True, False, True, True, False, True])
    for mix in ("pushsum", "mean", "ring", "none"):
        for t in range(4):
            P = mix_matrix(mix, t, 6, "exponential", act if mix != "none" else None)
            np.testing.assert_allclose(P.sum(axis=0), 1.0, atol=1e-9,
                                       err_msg=mix)
            # inactive clients: identity column AND row (no send, no recv)
            if mix != "none":
                for k in np.where(~act)[0]:
                    assert P[k, k] == 1.0 and P[:, k].sum() == 1.0
                    assert P[k, :].sum() == 1.0


def test_cwt_ring_is_pure_permutation():
    P = mix_matrix("ring", 0, 5, "exponential")
    assert ((P == 0) | (P == 1)).all() and (P.sum(axis=1) == 1).all()
    thetas = jnp.arange(5.0)[:, None]
    mixed, w = pushsum_mix(thetas, jnp.ones(5), P)
    # client k receives client k-1's model (cyclical weight transfer)
    np.testing.assert_allclose(np.asarray(mixed)[:, 0], [4., 0., 1., 2., 3.])
    np.testing.assert_allclose(np.asarray(w), 1.0)


# ---------------------------------------------------------------------------
# shard_map backend (1-device smoke; K=4 equivalence runs in the forced
# multi-device subprocess of test_system, if present)


def test_shard_map_backend_smoke(fed_data, mlp_spec, tmp_path):
    import os
    mesh = jax.make_mesh((1,), ("clients",))
    cfg = ProxyFLConfig(n_clients=1, rounds=1, batch_size=50, local_steps=2,
                        dp=DPConfig(enabled=False))
    vmap_eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                                   backend="vmap")
    eng = FederationEngine(
        cfg, n_clients=1, step_fns=vmap_eng.step_fns[0],
        init_fns=vmap_eng.init_fns[0], sample_fn=vmap_eng.sample_fn,
        backend="shard_map", mix="pushsum", mesh=mesh, axis="clients")
    key = jax.random.PRNGKey(0)
    state = eng.init_states(key)
    state, metrics = eng.run_round(state, fed_data[:1], 0, key)
    assert np.isfinite(metrics["loss"]).all()
    # snapshot gathers mesh-resident state off-device and restores bit-exact
    path = os.path.join(str(tmp_path), "snap")
    eng.save_state(path, state, 0, base_key=key)
    restored, done = eng.restore_state(path, like=eng.init_states(key),
                                       base_key=key)
    assert done == 1
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.fast
def test_save_restore_midrun_keeps_backend_equivalence(tmp_path, fed_data,
                                                       mlp_spec):
    """Checkpoint after round 0, restore, finish round 1: each backend's
    resumed trajectory is bit-identical to its own uninterrupted one, and
    loop==vmap equivalence survives the round trip."""
    import os
    cfg = ProxyFLConfig(n_clients=K, rounds=2, batch_size=50, local_steps=2,
                        dp=DPConfig(enabled=True))
    key = jax.random.PRNGKey(0)
    finals = {}
    for backend in ("loop", "vmap"):
        eng = dml_engine((mlp_spec,) * K, mlp_spec, cfg, backend=backend)
        state = eng.init_states(key)
        state, _ = eng.run_round(state, fed_data, 0,
                                 jax.random.fold_in(key, 10_000))
        path = os.path.join(str(tmp_path), backend)
        eng.save_state(path, state, 0, base_key=key)
        cont, _ = eng.run_round(state, fed_data, 1,
                                jax.random.fold_in(key, 10_001))
        restored, done = eng.restore_state(path, like=eng.init_states(key))
        assert done == 1
        resumed, _ = eng.run_round(restored, fed_data, 1,
                                   jax.random.fold_in(key, 10_001))
        np.testing.assert_array_equal(_flat_clients(cont),
                                      _flat_clients(resumed))
        finals[backend] = _flat_clients(resumed)
    np.testing.assert_allclose(finals["loop"], finals["vmap"],
                               atol=1e-5, rtol=1e-4)


@pytest.mark.fast
def test_loop_metrics_collate_heterogeneous_keys(fed_data):
    """Two architectures emitting DIFFERENT metric keys must collate to a
    union of keys with NaN fill, not raise KeyError (loop backend)."""
    def init(key):
        return {"proxy": {"params": {"a": jnp.zeros(3)}, "opt": ()},
                "w": jnp.ones((), jnp.float32)}

    def step_a(state, batch, key):
        return state, {"loss": jnp.float32(1.0), "aux_a": jnp.float32(2.0)}

    def step_b(state, batch, key):
        return state, {"loss": jnp.float32(3.0), "aux_b": jnp.float32(4.0)}

    cfg = ProxyFLConfig(n_clients=2, rounds=1, batch_size=4, local_steps=1,
                        dp=DPConfig(enabled=False))
    eng = FederationEngine(cfg, n_clients=2, step_fns=[step_a, step_b],
                           init_fns=[init, init],
                           sample_fn=lambda d, k, n_valid=None: d,
                           backend="loop", mix="none")
    state = eng.init_states(jax.random.PRNGKey(0))
    _, metrics = eng.run_round(state, [fed_data[0], fed_data[1]], 0,
                               jax.random.PRNGKey(1))
    assert set(metrics) == {"loss", "aux_a", "aux_b"}
    np.testing.assert_allclose(metrics["loss"], [1.0, 3.0])
    np.testing.assert_allclose(metrics["aux_a"], [2.0, np.nan])
    np.testing.assert_allclose(metrics["aux_b"], [np.nan, 4.0])
    # same union semantics when one client sits the round out: the union
    # covers ACTIVE clients' keys, the dropout's slots are NaN
    _, metrics = eng.run_round(state, [fed_data[0], fed_data[1]], 1,
                               jax.random.PRNGKey(2),
                               active=np.array([True, False]))
    assert set(metrics) == {"loss", "aux_a"}
    np.testing.assert_allclose(metrics["loss"], [1.0, np.nan])
    np.testing.assert_allclose(metrics["aux_a"], [2.0, np.nan])


def test_heterogeneous_requires_loop(fed_data, mlp_spec):
    vm = get_vision_model("lenet5")
    other = ModelSpec("lenet5", lambda k: vm.init(k, SHAPE, N_CLASSES),
                      vm.apply)
    cfg = ProxyFLConfig(n_clients=2, rounds=1, batch_size=50, local_steps=1,
                        dp=DPConfig(enabled=False))
    eng = dml_engine((mlp_spec, other), mlp_spec, cfg)  # auto -> loop
    assert eng.backend == "loop"
    with pytest.raises(AssertionError):
        dml_engine((mlp_spec, other), mlp_spec, cfg, backend="vmap")


def test_shard_map_state_is_placed_one_client_per_device(fed_data, mlp_spec):
    """The shard_map backend puts every stacked leaf on the mesh, split
    along the client axis, and keeps it there across rounds."""
    mesh = jax.make_mesh((1,), ("clients",))
    cfg = ProxyFLConfig(n_clients=1, rounds=1, batch_size=50, local_steps=1,
                        dp=DPConfig(enabled=False))
    vmap_eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                                   backend="vmap")
    eng = FederationEngine(
        cfg, n_clients=1, step_fns=vmap_eng.step_fns[0],
        init_fns=vmap_eng.init_fns[0], sample_fn=vmap_eng.sample_fn,
        backend="shard_map", mix="pushsum", mesh=mesh, axis="clients")
    key = jax.random.PRNGKey(0)
    split = jax.sharding.NamedSharding(eng.mesh,
                                       jax.sharding.PartitionSpec("clients"))
    state = eng.init_states(key)
    for _ in range(2):
        for x in jax.tree_util.tree_leaves(state):
            assert x.sharding.is_equivalent_to(split, x.ndim)
        state, _ = eng.run_round(state, fed_data[:1], 0, key)


_FOUR_DEVICES = """
import jax, numpy as np
from repro.launch import train
args = ["--arch", "qwen1.5-4b", "--smoke", "--clients", "4", "--rounds", "2",
        "--steps-per-round", "1", "--batch", "2", "--seq", "16",
        "--rounds-per-block", "2"]
sm = train.run(args + ["--backend", "shard_map"])["state"]
for x in jax.tree_util.tree_leaves(sm):
    assert x.sharding.shard_shape(x.shape)[0] == 1, (x.shape, x.sharding)
    assert len(x.sharding.device_set) == 4
vm = train.run(args + ["--backend", "vmap"])["state"]
for a, b in zip(jax.tree_util.tree_leaves(sm), jax.tree_util.tree_leaves(vm)):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), atol=2e-2)
print("FOUR_DEVICES_OK")
"""


def test_shard_map_runs_one_client_per_device_on_four_devices():
    """On four (virtual CPU) devices the shard_map backend keeps one
    client's state on each device through a round-block and lands close to
    the vmap backend (bf16 smoke model: one bf16 ulp). Runs in a child:
    the device count is fixed when JAX starts."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(root, "src"))
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES], env=env,
                       cwd=root, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0 and "FOUR_DEVICES_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-4000:]


def test_round_donates_the_state(fed_data, mlp_spec):
    """Rounds update the state in place on every platform: the state handed
    to a round is deleted, so a caller that reads it fails here exactly as
    it would on the chip."""
    cfg = ProxyFLConfig(n_clients=K, rounds=1, batch_size=50, local_steps=1,
                        dp=DPConfig(enabled=False))
    eng = single_model_engine(mlp_spec, cfg, False, mix="pushsum",
                              backend="vmap")
    key = jax.random.PRNGKey(0)
    old = eng.init_states(key)
    new, _ = eng.run_round(old, fed_data, 0, key)
    assert all(x.is_deleted() for x in jax.tree_util.tree_leaves(old))
    assert not any(x.is_deleted() for x in jax.tree_util.tree_leaves(new))
