"""The LLM driver's in-process entry (``repro.launch.train.run``), its
persistent compile cache, and the platform refusal of CPU-only figures."""
import jax
import numpy as np
import pytest

from repro.launch import compile_cache, train

SMOKE = ["--arch", "qwen1.5-4b", "--smoke", "--clients", "2",
         "--steps-per-round", "1", "--batch", "2", "--seq", "16"]


def test_run_returns_blocks_metrics_and_state():
    """Blocks are timed one by one and every round's per-client metrics
    come back; 3 rounds in blocks of 2 are two blocks."""
    res = train.run(SMOKE + ["--rounds", "3", "--rounds-per-block", "2"])
    assert len(res["block_seconds"]) == 2
    assert all(s > 0 for s in res["block_seconds"])
    for name in ("private_loss", "proxy_loss"):
        assert res["metrics"][name].shape == (3, 2)
        assert np.isfinite(res["metrics"][name]).all()
    np.testing.assert_allclose(np.asarray(res["state"]["w"]).sum(), 2.0,
                               rtol=1e-6)


def test_shard_map_needs_a_device_per_client():
    with pytest.raises(SystemExit, match="one device per client"):
        train.run(SMOKE + ["--rounds", "1", "--backend", "shard_map"])


def test_compile_cache_keeps_the_env_directory(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.use_compile_cache()
        assert path == str(compile_cache.CHECKOUT / ".jax_cache")
        assert (compile_cache.CHECKOUT / "src" / "repro").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_fig_hier_refuses_on_a_tpu_host(monkeypatch):
    """fig_hier times CPU virtual devices in a child process; on a TPU host
    that would report CPU times, so it refuses before starting the child."""
    from benchmarks import fig_hier

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU host"):
        fig_hier.run()
