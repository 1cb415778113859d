"""Sharding-rule unit tests on an AbstractMesh (no devices needed): the
PartitionSpecs produced for every full-size architecture must divide the
tensor dims they shard, and the placement policy (row/column parallel,
expert parallel, vocab-sharded embeddings, tp/zero1/zero3 modes) must hold."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, list_archs
from repro.configs.base import DPConfig, InputShape, ProxyFLConfig
from repro.configs.registry import proxy_of
from repro.launch.sharding import (batch_pspec, cache_pspecs, choose_mode,
                                   param_pspec, tree_pspecs)


MESH = jax.sharding.AbstractMesh((16, 16), ("data", "model"))
MESH3 = jax.sharding.AbstractMesh((2, 16, 16), ("pod", "data", "model"))
SIZES = {"data": 16, "model": 16}


def _check_divisible(tree, specs):
    flat_s, _ = jax.tree_util.tree_flatten(tree)
    flat_p, _ = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, P))
    for sds, spec in zip(flat_s, flat_p):
        for d, entry in enumerate(spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            n = 1
            for a in axes:
                n *= SIZES.get(a, 2)
            assert sds.shape[d] % n == 0, (sds.shape, spec, d)


@pytest.mark.parametrize("arch", list_archs())
def test_param_specs_divide(arch):
    from repro.launch.steps import StepOptions, train_state_shapes
    cfg = get_config(arch)
    shapes = train_state_shapes(cfg, proxy_of(cfg),
                                ProxyFLConfig(dp=DPConfig()), StepOptions())
    for fsdp in (False, True):
        specs = tree_pspecs(shapes["private"]["params"], MESH, fsdp_data=fsdp)
        _check_divisible(shapes["private"]["params"], specs)


def test_row_parallel_on_input_dim():
    spec = param_pspec("prefix/0/mixer/wo/w", (4096, 1024), MESH)
    assert spec[0] == "model"  # contraction dim sharded (row parallel)
    spec = param_pspec("prefix/0/mixer/wq/w", (1024, 4096), MESH)
    assert spec[1] == "model"  # output dim sharded (column parallel)


def test_embed_vocab_sharded():
    spec = param_pspec("embed/e", (102400, 5120), MESH)
    assert spec[0] == "model"
    # audio codebook tables are [K, V, d]
    spec = param_pspec("embed/e", (4, 2048, 1536), MESH)
    assert spec[1] == "model"


def test_stack_dim_never_sharded():
    spec = param_pspec("stack/0/ffn/gate/w", (28, 3584, 18944), MESH)
    assert spec[0] is None


def test_small_tensors_replicated():
    spec = param_pspec("prefix/0/norm1/g", (4096,), MESH)
    assert all(s is None for s in spec)


def test_expert_parallel_flag():
    shape = (30, 160, 5120, 1536)  # [stack, experts, d, d_ff]
    tp = param_pspec("stack/0/ffn/gate", shape, MESH, expert_parallel=False)
    ep = param_pspec("stack/0/ffn/gate", shape, MESH, expert_parallel=True)
    assert ep[1] == "model"
    assert tp[1] != "model"


def test_client_stacked_pod_leading():
    spec = param_pspec("stack/0/ffn/gate/w", (2, 28, 3584, 18944), MESH3,
                       client_stacked=True)
    assert spec[0] == "pod"
    assert spec[1] is None  # stack dim after the client dim


def test_choose_mode_thresholds():
    small = {"w": jax.ShapeDtypeStruct((1000, 1000), jnp.float32)}  # 4MB
    assert choose_mode(small, MESH) == "tp"
    big = {"w": jax.ShapeDtypeStruct((200_000, 8192), jnp.bfloat16)}  # 3.3GB
    # params/16 (0.2GB) fits a 1GB budget; params+opt/16 (~1.4GB) doesn't
    assert choose_mode(big, MESH, budget_bytes=1.0e9) == "zero1"
    assert choose_mode(big, MESH, budget_bytes=0.1e9) == "zero3"


def test_batch_pspec_long_context():
    # batch=1: shard the sequence dim instead
    spec = batch_pspec((1, 524288), MESH)
    assert spec[0] is None and spec[1] is not None
    spec = batch_pspec((256, 4096), MESH)
    assert spec[0] is not None


def test_cache_specs_divide():
    from repro.launch.steps import serve_state_shapes
    cfg = get_config("gemma3-4b")
    shapes = serve_state_shapes(cfg, InputShape("d", 32768, 128, "decode"))
    specs = cache_pspecs(shapes["cache"], MESH)
    _check_divisible(shapes["cache"], specs)


@pytest.mark.parametrize("arch", ["qwen1.5-110b", "deepseek-v2-236b",
                                  "arctic-480b", "jamba-1.5-large-398b"])
def test_big_archs_get_zero3(arch):
    from repro.launch.steps import StepOptions, train_state_shapes
    cfg = get_config(arch)
    shapes = train_state_shapes(cfg, proxy_of(cfg),
                                ProxyFLConfig(dp=DPConfig()), StepOptions())
    assert choose_mode(shapes["private"]["params"], MESH) == "zero3"


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "qwen2-7b", "gemma3-4b",
                                  "falcon-mamba-7b", "musicgen-medium"])
def test_small_archs_replicate(arch):
    from repro.launch.steps import StepOptions, train_state_shapes
    cfg = get_config(arch)
    shapes = train_state_shapes(cfg, proxy_of(cfg),
                                ProxyFLConfig(dp=DPConfig()), StepOptions())
    assert choose_mode(shapes["private"]["params"], MESH) in ("tp", "zero1")
