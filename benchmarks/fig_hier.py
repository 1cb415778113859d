"""Two-level hier gossip: rounds/sec vs flat backends at large K (beyond-paper).

The engine's ``backend="hier"`` targets thousand-client cohorts: the flat
PushSum matrix P^(t) is factored into a block-diagonal intra-shard part
(mixed on device as one batched [S, L, L] matmul over the stacked clients)
plus at most one sparse cross-shard edge per client per round (the
ppermute-shaped permutation that becomes inter-node traffic in
production). This figure measures what the factoring buys on a forced
8-device host mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=8``,
set in a SUBPROCESS worker because jax locks the device count at first
initialization):

* rounds/sec of hier (n_shards=8, blocked) vs flat vmap (blocked) vs flat
  loop (per-round dispatch — the B=1 baseline) at K ∈ {8, 64, 256}
  (full budget adds 1024);
* flat shard_map for reference at K=8 ONLY — its one-client-per-device
  layout cannot exceed the 8-device host mesh, which is exactly the
  scaling wall the two-level layout removes (logged in the row);
* the analytic per-client CROSS-SHARD wire bytes per round, which stay
  O(D) — flat in K — while the intra-shard mass movement never leaves the
  device;
* hier at τ=2 (cross-shard staleness). HONESTY CAVEAT, carried in the
  rows: on this CPU simulator τ>0 overlaps no real network latency — it
  removes the cross-shard data dependency from the compiled schedule, but
  the wall-clock win only materializes with genuine inter-node latency
  (the τ=0/τ=2 ratio here bounds the scheduling overhead, nothing more).

Results are written as JSON to ``results/fig_hier.json`` (override with
``REPRO_BENCH_HIER_JSON``); the acceptance metric is
``speedup_vs_loop`` of the hier τ=0 row at K=256.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

FULL = bool(int(os.environ.get("REPRO_BENCH_FULL", "0")))

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MARK = "FIG_HIER_JSON "
_DEVICES = 8

#: tiny synthetic classification task — the timing target is the ROUND
#: machinery (mix factoring, host dispatch), not the model math
_SHAPE, _N_CLASSES, _PER_CLIENT = (8, 8, 1), 4, 32


def _worker(full: bool) -> list:
    """Runs inside the subprocess with the forced 8-device host mesh."""
    import time

    import jax
    import numpy as np

    from benchmarks.common import spec_of
    from benchmarks.fig_blocks import _time_blocks
    from repro.configs.base import DPConfig, ProxyFLConfig
    from repro.core.engine import FederationEngine, dml_engine
    from repro.core.gossip import hier_mix_schedule
    from repro.data.synthetic import make_classification_data
    from repro.nn.modules import tree_flatten_vector

    n_dev = jax.device_count()
    spec = spec_of("mlp", _SHAPE, _N_CLASSES)
    D = int(tree_flatten_vector(spec.init(jax.random.PRNGKey(0))).shape[0])
    key = jax.random.PRNGKey(0)

    def data_of(K):
        x, y = make_classification_data(
            jax.random.PRNGKey(1), _PER_CLIENT * K, _SHAPE, _N_CLASSES,
            sep=2.0, task_seed=7)
        return [(x[k * _PER_CLIENT:(k + 1) * _PER_CLIENT],
                 y[k * _PER_CLIENT:(k + 1) * _PER_CLIENT])
                for k in range(K)]

    def cfg_of(K, rounds, *, n_shards=1, staleness=0):
        # gossip-bound regime (local_steps=1), as in fig_blocks: the claim
        # under test is round machinery, not step math
        return ProxyFLConfig(n_clients=K, rounds=rounds, local_steps=1,
                             batch_size=8, seed=0, n_shards=n_shards,
                             staleness=staleness, dp=DPConfig(enabled=False))

    def cross_bytes_per_client(K, S, rounds):
        """Mean analytic cross-shard f32 wire bytes per client per round:
        (#cross edges / K) · 2 · 4·D (value vector out + the mirrored w
        scalar is noise; ×2 for the send being received) — bounded by O(D)
        independent of K."""
        _, _, scale = hier_mix_schedule("pushsum", 0, rounds, K, S)
        frac_cross = float((np.asarray(scale) > 0).mean())
        return frac_cross * 4 * D

    Ks = (8, 64, 256, 1024) if full else (8, 64, 256)
    rounds, block = 8, 8
    shards = _DEVICES
    rows = []
    for K in Ks:
        data = data_of(K)
        base_loop = None
        # loop = the flat per-round-dispatch baseline (B=1 by definition)
        loop_rounds = 4 if K >= 256 else rounds
        eng = dml_engine((spec,) * K, spec, cfg_of(K, loop_rounds),
                         backend="loop")
        sec = _time_blocks(eng, data, key, loop_rounds, 1,
                           trials=2 if K >= 256 else 3)
        base_loop = sec
        rows.append(dict(figure="fig_hier", K=K, backend="loop",
                         n_shards=1, staleness=0, rounds_per_block=1,
                         devices=n_dev, sec_per_round=round(sec, 5),
                         rounds_per_sec=round(1.0 / sec, 2),
                         speedup_vs_loop=1.0,
                         bytes_cross_per_client=None, note=""))

        grid = [("vmap", 1, 0), ("hier", shards, 0), ("hier", shards, 2)]
        for backend, S, tau in grid:
            eng = dml_engine((spec,) * K, spec,
                             cfg_of(K, rounds, n_shards=S, staleness=tau),
                             backend=backend)
            sec = _time_blocks(eng, data, key, rounds, block)
            note = ""
            if tau:
                note = ("CPU simulator: tau>0 overlaps no real network "
                        "latency; wall-clock win needs genuine inter-node "
                        "latency")
            rows.append(dict(
                figure="fig_hier", K=K, backend=backend, n_shards=S,
                staleness=tau, rounds_per_block=block, devices=n_dev,
                sec_per_round=round(sec, 5),
                rounds_per_sec=round(1.0 / sec, 2),
                speedup_vs_loop=round(base_loop / sec, 2),
                bytes_cross_per_client=(
                    round(cross_bytes_per_client(K, S, rounds), 1)
                    if backend == "hier" else None),
                note=note))

        if K == n_dev:
            # flat shard_map: one client per device — CANNOT scale past
            # the 8-device host mesh; measured at K=8 for reference only
            vmap_eng = dml_engine((spec,) * K, spec, cfg_of(K, rounds),
                                  backend="vmap")
            mesh = jax.make_mesh((K,), ("clients",))
            eng = FederationEngine(
                cfg_of(K, rounds), n_clients=K,
                step_fns=vmap_eng.step_fns[0], init_fns=vmap_eng.init_fns[0],
                sample_fn=vmap_eng.sample_fn, backend="shard_map",
                mix="pushsum", mesh=mesh, axis="clients")
            sec = _time_blocks(eng, data, key, rounds, block)
            rows.append(dict(
                figure="fig_hier", K=K, backend="shard_map", n_shards=K,
                staleness=0, rounds_per_block=block, devices=n_dev,
                sec_per_round=round(sec, 5),
                rounds_per_sec=round(1.0 / sec, 2),
                speedup_vs_loop=round(base_loop / sec, 2),
                bytes_cross_per_client=round(4.0 * D, 1),
                note="one client per device: bounded by the 8-device host "
                     "mesh — the flat layout cannot reach K=64+"))
    return rows


def run(full: bool = FULL):
    """Spawn the worker with the forced host-device mesh (jax locks the
    device count at first init, and this parent process has already
    initialized jax via the other figure modules). The worker times the
    CPU's virtual devices, so on a host with a TPU the figure refuses
    instead of reporting CPU times there."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "fig_hier times an 8-device CPU host mesh in a child process; "
            "on a TPU host that would report CPU times, so it refuses")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_DEVICES}"
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["REPRO_BENCH_FULL"] = "1" if full else "0"
    pp = env.get("PYTHONPATH", "")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO, "src"), _REPO] + ([pp] if pp else []))
    cmd = [sys.executable, "-m", "benchmarks.fig_hier"]
    r = subprocess.run(cmd, cwd=_REPO, env=env, capture_output=True,
                       text=True)
    marked = [l for l in r.stdout.splitlines() if l.startswith(_MARK)]
    if r.returncode != 0 or not marked:
        raise RuntimeError(
            f"fig_hier worker failed (rc={r.returncode}):\n"
            f"{r.stdout[-2000:]}\n{r.stderr[-4000:]}")
    rows = json.loads(marked[-1][len(_MARK):])
    path = os.environ.get("REPRO_BENCH_HIER_JSON",
                          os.path.join(_REPO, "results", "fig_hier.json"))
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main(argv=None) -> int:
    # worker entry: force the host-device mesh BEFORE jax initializes
    # (harmless if the parent already set it in our env)
    os.environ.setdefault(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={_DEVICES}")
    rows = _worker(FULL)
    print(_MARK + json.dumps(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
