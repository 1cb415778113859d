"""Benchmark-harness regressions: process-independent synthetic task seeds
(crc32, not salted ``hash()``), ragged Dirichlet federation_data (no
truncation, disjoint, nonempty), per-method proxy-accuracy aggregation
across seeds in ``bench_methods``, and the run.py registry staying in sync
with the fig_* modules on disk."""
import glob
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

import benchmarks.common as common

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.fast
def test_run_registry_lists_every_fig_module(capsys):
    """Every fig_* benchmark module present on disk must be registered in
    ``benchmarks.run.MODULES`` and appear in ``run.py --list`` with a
    one-line description — new figures can't be silently unregistered."""
    import benchmarks.run as run
    on_disk = {os.path.basename(p)[:-3] for p in
               glob.glob(os.path.join(REPO, "benchmarks", "fig*.py"))}
    assert on_disk, "no fig_* modules found — wrong repo layout?"
    missing = on_disk - set(run.MODULES)
    assert not missing, f"fig modules not registered in run.py: {missing}"

    assert run.main(["--list"]) == 0
    out = capsys.readouterr().out
    lines = {l.split(":", 1)[0]: l.split(":", 1)[1].strip()
             for l in out.strip().splitlines()}
    assert set(lines) == set(run.MODULES)
    for name in on_disk:
        assert name in lines, f"{name} absent from --list output"
        # "[anchor] docstring first line" — both halves non-trivial
        assert len(lines[name]) > len("[x] "), f"{name}: empty description"


@pytest.mark.fast
def test_run_registry_tiers_cover_every_module(capsys):
    """Every registry entry carries a runtime tier, the tier shows up in
    ``--list``, and ``names_for_tier`` partitions the registry — the hook
    ``benchmarks.run --tier`` selects figures through."""
    import benchmarks.run as run
    for name, entry in run.MODULES.items():
        assert len(entry) == 3, f"{name}: registry entry missing tier field"
        assert entry[2] in run.TIERS, f"{name}: unknown tier {entry[2]!r}"
    fast = run.names_for_tier("fast")
    full = run.names_for_tier("full")
    assert set(fast) | set(full) == set(run.MODULES)
    assert not set(fast) & set(full)
    # the fast tier: the figures that finish in CPU minutes, hier included
    assert {"fig_blocks", "fig_kernels", "fig_hier"} <= set(fast)
    with pytest.raises(ValueError, match="tier"):
        run.names_for_tier("nope")
    assert run.main(["--list"]) == 0
    out = capsys.readouterr().out
    for line in out.strip().splitlines():
        name = line.split(":", 1)[0]
        assert f"({run.MODULES[name][2]})" in line, \
            f"{name}: tier absent from --list line"


def test_task_seed_is_process_independent():
    """``hash(str)`` is salted per interpreter: two processes with
    different PYTHONHASHSEED must still agree on the task seed, or every
    benchmark process trains on a DIFFERENT synthetic dataset."""
    code = ("import sys; sys.path[:0] = ['src', '.'];"
            "from benchmarks.common import task_seed_of;"
            "print(task_seed_of('kvasir'), task_seed_of('camelyon'))")
    outs = []
    for hashseed in ("0", "4242"):
        env = dict(os.environ, PYTHONHASHSEED=hashseed, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        outs.append(r.stdout.strip())
    assert outs[0] == outs[1], f"task seed depends on hash salt: {outs}"
    assert outs[0] == (f"{common.task_seed_of('kvasir')} "
                       f"{common.task_seed_of('camelyon')}")


@pytest.mark.fast
def test_federation_data_dirichlet_is_ragged_untruncated():
    data, (xt, yt), d = common.federation_data("kvasir", 4, seed=0,
                                               n_train_factor=0.1)
    sizes = [dk[0].shape[0] for dk in data]
    per_client = int(d["per_client"] * 0.1)
    assert sum(sizes) == per_client * 4      # every partitioned sample kept
    assert len(set(sizes)) > 1               # genuinely size-skewed
    assert min(sizes) >= 1                   # sampleable on every backend
    for dk in data:
        assert dk[0].shape[1:] == d["shape"]


@pytest.mark.fast
def test_ensure_nonempty_moves_sample_from_largest():
    rng = np.random.default_rng(0)
    idxs = [np.arange(10), np.array([], np.int64), np.arange(10, 13)]
    fixed = common._ensure_nonempty(rng, idxs)
    allv = np.concatenate(fixed)
    assert all(len(i) >= 1 for i in fixed)
    assert sorted(allv.tolist()) == list(range(13))  # nothing lost or duped


@pytest.mark.fast
def test_ensure_nonempty_does_not_reempty_donors():
    """Donating must not hollow out an earlier client: [[5], [], []] needs
    repeated passes, not one forward sweep."""
    rng = np.random.default_rng(0)
    idxs = [np.array([5]), np.array([], np.int64), np.array([], np.int64)]
    with pytest.raises(ValueError, match="fewer samples than clients"):
        common._ensure_nonempty(rng, idxs)
    idxs = [np.array([5, 6, 7]), np.array([], np.int64),
            np.array([], np.int64)]
    fixed = common._ensure_nonempty(rng, idxs)
    assert all(len(i) >= 1 for i in fixed)
    assert sorted(np.concatenate(fixed).tolist()) == [5, 6, 7]


@pytest.mark.fast
def test_bench_methods_aggregates_proxy_acc_across_seeds(monkeypatch):
    """The ``-proxy`` row must average over ALL seeds (the old code kept
    only the last seed's value), and must not leak into later methods."""
    def fake_federation_data(dataset, n_clients, seed, **kw):
        x = jnp.zeros((6, 2, 2, 1))
        y = jnp.zeros((6,), jnp.int32)
        return ([(x, y)] * n_clients, (x, y),
                {"shape": (2, 2, 1), "n_classes": 2})

    def fake_run_federated(method, specs, prox, client_data, test, cfg,
                           **kw):
        seed = kw.get("seed", 0)
        if method in ("proxyfl", "fml"):
            row = {"round": 1, "private_acc": [0.5 + seed],
                   "proxy_acc": [0.1 * (seed + 1)]}
        else:
            row = {"round": 1, "acc": [0.3]}
        # seed 0 holds the worst (largest) per-client epsilon
        return {"history": [row], "epsilon": [9.0 - seed, 3.0],
                "clients": []}

    monkeypatch.setattr(common, "federation_data", fake_federation_data)
    monkeypatch.setattr(common, "run_federated", fake_run_federated)
    rows = common.bench_methods("mnist", ("proxyfl", "fedavg"), n_clients=2,
                                rounds=1, seeds=(0, 1), dp=False)
    by_method = {r["method"]: r for r in rows}
    # mean over seeds {0.1, 0.2}, not the last seed's 0.2
    assert by_method["proxyfl-proxy"]["acc_mean"] == pytest.approx(0.15)
    assert by_method["proxyfl"]["acc_mean"] == pytest.approx(1.0)
    assert "fedavg-proxy" not in by_method  # no stale cross-method leak
    assert set(by_method) == {"proxyfl", "proxyfl-proxy", "fedavg"}
    # epsilon: worst case over clients AND seeds (9.0 from seed 0), not
    # the last seed's value
    assert by_method["proxyfl"]["epsilon"] == pytest.approx(9.0)
