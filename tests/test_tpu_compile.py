"""Compile the main-path Pallas kernels and round programs for TPU v5e chips
that are described, not attached (``jax.experimental.topologies``), at real
widths and with ``interpret=False``: what Mosaic refuses here — misaligned
blocks, scalar stores to VMEM, scoped-VMEM overflow — would otherwise first
fail on the chip. Nothing runs, so these tests say nothing about results or
times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may load the TPU library, and under
pytest-xdist only the worker given this file loads it."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.kernels import dp_clip, dp_step, pushsum_mix

N_SMALL = 1000


@contextlib.contextmanager
def _described_v5e():
    """The devices of a described ``v5e:2x2``, with the persistent compile
    cache switched off while they are in use."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU executable written to the persistent cache cannot be read back
    # without a chip: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip():
    with _described_v5e() as devices:
        yield SingleDeviceSharding(devices[0])


@pytest.fixture(scope="module")
def four_chips():
    """A one-axis mesh over the four chips, one client per chip."""
    with _described_v5e() as devices:
        yield jax.sharding.Mesh(np.array(devices[:4]), ("clients",))


@pytest.fixture(scope="module")
def proxy_size():
    """Flat parameter count of the ``--preset 100m`` proxy (d256 x 4)."""
    from repro.configs.registry import proxy_of
    from repro.launch.train import preset_100m
    from repro.nn.model import init_model

    proxy = proxy_of(preset_100m(), n_layers=4, d_model=256)
    shapes = jax.eval_shape(lambda k: init_model(k, proxy),
                            jax.random.PRNGKey(0))
    return sum(x.size for x in jax.tree_util.tree_leaves(shapes))


def _compile_text(fn, *shapes, sharding) -> str:
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _dp_kernels(n):
    """name -> (fn over 1-D vectors, number of [n] operands)."""
    hyper = dict(stddev=1.0, n_units=8, lr=1e-3, weight_decay=1e-4)
    return {
        "sumsq": (lambda x: dp_clip.sumsq(x, interpret=False), 1),
        "scale_accumulate": (lambda a, g: dp_clip.scale_accumulate(
            a, g, jnp.sum(g[:1]), interpret=False), 2),
        "noise_sgd_step": (lambda a, z, p: dp_step.noise_sgd_step(
            a, z, p, interpret=False, **hyper), 3),
        "noise_adam_step": (lambda a, z, p, m, v: dp_step.noise_adam_step(
            a, z, p, m, v, c1=jnp.sum(m[:1]), c2=jnp.sum(v[:1]),
            interpret=False, **hyper), 5),
    }


@pytest.mark.parametrize("size", ["small", "proxy"])
@pytest.mark.parametrize("kernel", ["sumsq", "scale_accumulate",
                                    "noise_sgd_step", "noise_adam_step"])
def test_dp_kernel_compiles(one_chip, proxy_size, kernel, size):
    n = N_SMALL if size == "small" else proxy_size
    fn, n_args = _dp_kernels(n)[kernel]
    txt = _compile_text(fn, *[(n,)] * n_args, sharding=one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("kernel", ["sumsq", "scale_accumulate",
                                    "noise_adam_step"])
def test_dp_kernel_compiles_vmapped_over_clients(one_chip, proxy_size,
                                                 kernel):
    """The engine vmaps the fused DP step over the clients, which prepends
    a batch dim to every block and every SMEM scalar operand."""
    fn, n_args = _dp_kernels(proxy_size)[kernel]
    txt = _compile_text(jax.vmap(fn), *[(8, proxy_size)] * n_args,
                        sharding=one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("K", [4, 64, 256])
def test_fused_pushsum_mix_compiles(one_chip, proxy_size, K):
    txt = _compile_text(
        lambda f, w, P: pushsum_mix.fused_pushsum_mix(f, w, P,
                                                      interpret=False),
        (K, proxy_size), (K,), (K, K), sharding=one_chip)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("K", [4, 64])
def test_fused_stale_mix_compiles(one_chip, proxy_size, K):
    txt = _compile_text(
        lambda f, w, kept, sent, b, bw: pushsum_mix.fused_stale_mix(
            f, w, kept, sent, b, bw, interpret=False),
        (K, proxy_size), (K,), (K,), (K, K), (K, proxy_size), (K,),
        sharding=one_chip)
    assert "tpu_custom_call" in txt


def test_mix_block_fits_scoped_vmem():
    """The chunk width narrows with K, stays a multiple of 128, and keeps
    the double-buffered blocks inside the budget."""
    for K in (1, 4, 64, 256, 1024):
        for itemsize in (2, 4):
            b = pushsum_mix.mix_block(K, itemsize, 2, 2, 8192)
            assert b % 128 == 0 and 128 <= b <= 8192
            if b > 128:
                assert K * b * (4 * itemsize + 8) <= \
                    pushsum_mix.VMEM_BLOCK_BUDGET
    assert pushsum_mix.mix_block(4, 4, 2, 2, 8192) == 8192
    assert pushsum_mix.mix_block(256, 4, 2, 2, 8192) == 1280


def test_llm_round_block_fits_one_v5e(one_chip, monkeypatch):
    """The round-block program ``repro.launch.train`` runs at the 100m
    preset (4 clients, DP proxy, fused exchange, 2 rounds per block) fits
    one v5e's HBM: its outputs alias its arguments (the state is donated),
    and its peak stays under 12 GiB (11.15 GiB when this test was written;
    a second copy of the 5.6 GiB client state, or training without remat,
    does not fit the 15.75 GiB the compiler grants)."""
    import repro.kernels
    from repro.configs.base import DPConfig, ProxyFLConfig
    from repro.configs.registry import proxy_of
    from repro.launch.train import make_engine, preset_100m

    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    K, B, S, T, steps = 4, 8, 128, 2, 2
    cfg = preset_100m()
    fl = ProxyFLConfig(n_clients=K, local_steps=steps, batch_size=B,
                       use_pallas=True,
                       dp=DPConfig(enabled=True, clip_norm=1.0,
                                   noise_multiplier=1.0))
    eng = make_engine(cfg, proxy_of(cfg, n_layers=4, d_model=256), fl)
    block = eng._build_block(T, steps, eng._mix_matmul_op())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(eng.init_states, jax.random.PRNGKey(0)))
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    compiled = block.lower(
        state, sds((K, 64, S + 1), jnp.int32), sds((K,), jnp.int32),
        sds((K,), jnp.int32), sds((T, K, K), jnp.float32),
        sds((T, K), jnp.bool_), sds((T,), jnp.int32),
        sds(key.shape, key.dtype)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes  # tile padding
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 12 * 2 ** 30, peak


def test_llm_round_block_ops_carry_the_program_scopes(one_chip):
    """Compiled for the chip, every matmul of the LLM round-block (vmap
    backend, DP proxy) names the local phase or the exchange in its
    metadata, and the step's scopes all reach the compiled program: the
    benchmark's by-scope reduction reads these names from the device
    trace's op metadata."""
    from repro.configs import get_config
    from repro.configs.base import DPConfig, ProxyFLConfig
    from repro.configs.registry import proxy_of, smoke_variant
    from repro.launch.train import make_engine

    K, B, S, T, steps = 2, 2, 16, 2, 1
    cfg = smoke_variant(get_config("qwen1.5-4b"))
    fl = ProxyFLConfig(n_clients=K, local_steps=steps, batch_size=B,
                       dp=DPConfig(enabled=True))
    eng = make_engine(cfg, smoke_variant(proxy_of(cfg)), fl)
    block = eng._build_block(T, steps, eng._mix_matmul_op())

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(eng.init_states, key))
    hlo = block.lower(
        state, sds((K, 4, S + 1), jnp.int32), sds((K,), jnp.int32),
        sds((K,), jnp.int32), sds((T, K, K), jnp.float32),
        sds((T, K), jnp.bool_), sds((T,), jnp.int32),
        sds(key.shape, key.dtype)).compile().as_text()
    matmuls = [line for line in hlo.splitlines()
               if re.search(r"= \S+ (dot|convolution)\(", line)]
    assert matmuls
    assert [m for m in matmuls
            if not re.search(r'op_name="[^"]*fl\.(local|exchange)/', m)] == []
    names = re.findall(r'op_name="([^"]*)"', hlo)
    for scope in ("fl.private", "fl.proxy", "fl.adam", "fl.loss",
                  "fl.attention", "fl.exchange"):
        assert any(scope in n for n in names), scope


def test_dml_loss_head_compiles_without_reduce_window(one_chip):
    """The DML loss head over Phi-3-width logits ``[4 clients, 4, 512,
    8016]``, vmapped over the clients with its gradient, as the round-block
    runs it. The own logits come out of the head's matmul, which lays them
    out with the vocabulary on sublanes; a log-softmax whose row max the
    compiler turns into a reduce-window as wide as the row is quadratic in
    V there, and took the largest share of the round on the chip."""
    from repro.nn.losses import dml_loss

    K, B, S, d, V = 4, 4, 512, 3072, 8016

    def loss(w, h, peer, labels):
        return dml_loss(h @ w, peer, labels, 0.5)

    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    hlo = jax.jit(jax.vmap(jax.value_and_grad(loss))).lower(
        sds((K, d, V)), sds((K, B, S, d)), sds((K, B, S, V)),
        sds((K, B, S), jnp.int32)).compile().as_text()
    assert "exponential" in hlo
    assert "reduce-window" not in hlo


# name -> what the case changes from pushsum over the exponential graph with
# a DP proxy and four rounds a block; "mix" goes to FederationEngine,
# "rounds" sets the block, the rest goes to the config. Every exchange here
# repeats within two rounds (the exponential graph over four clients
# alternates two shifts), and every round unrolled adds to the compile.
FOUR_CHIP_CASES = {
    "pushsum_dp": {},
    "pushsum_nodp": {"dp": False, "rounds": 2},
    "pushsum_ring_graph": {"topology": "ring", "rounds": 2},
    "mix_ring": {"mix": "ring", "rounds": 2},
    "mix_none": {"mix": "none", "rounds": 2},
    "dropout": {"dropout_rate": 0.25},
}


@pytest.mark.parametrize("case", list(FOUR_CHIP_CASES))
def test_shard_map_round_block_compiles_for_four_v5e_chips(four_chips, case,
                                                           monkeypatch):
    """The shard_map backend's round program for the LLM federation, one
    client per chip of four, compiles for the chips: its exchange is
    collective-permutes exactly when the case mixes, the state is donated,
    and the per-chip peak fits a v5e. With dropout the backend runs one
    round per program, so that program is the one compiled, at a round
    where some but not all clients take part."""
    import repro.kernels
    from repro.configs import get_config
    from repro.configs.base import DPConfig, ProxyFLConfig
    from repro.configs.registry import proxy_of, smoke_variant
    from repro.core.engine import FederationEngine, active_schedule
    from repro.launch.train import make_engine

    monkeypatch.setattr(repro.kernels, "default_interpret", lambda: False)
    kw = FOUR_CHIP_CASES[case]
    K, B, S, T, steps = 4, 2, 16, kw.get("rounds", 4), 1
    cfg = smoke_variant(get_config("qwen1.5-4b"))
    fl = ProxyFLConfig(n_clients=K, local_steps=steps, batch_size=B,
                       topology=kw.get("topology", "exponential"),
                       dropout_rate=kw.get("dropout_rate", 0.0),
                       dp=DPConfig(enabled=kw.get("dp", True)))
    eng = make_engine(cfg, smoke_variant(proxy_of(cfg)), fl,
                      backend="shard_map", mesh=four_chips)
    if "mix" in kw:
        eng = FederationEngine(fl, n_clients=K, step_fns=eng.step_fns,
                               init_fns=eng.init_fns, sample_fn=eng.sample_fn,
                               backend="shard_map", mix=kw["mix"],
                               mesh=eng.mesh)
    mixing = eng.mix != "none"
    place = NamedSharding(eng.mesh, PartitionSpec(eng.axis))
    one = NamedSharding(eng.mesh, PartitionSpec())

    def sds(shape, dtype, sharding=place):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype), jax.eval_shape(eng.init_states, key))
    head = (state, sds((K, 8, S + 1), "int32"), sds((K,), "int32"),
            sds((K,), "int32", one))
    if fl.dropout_rate:
        sched = active_schedule(0, 16, K, fl)
        t = next(t for t, a in enumerate(sched) if 1 < a.sum() < K)
        program = eng._build_round(
            steps, eng._shard_mix_op(t, tuple(bool(a) for a in sched[t])))
        tail = (sds((0,), "float32", one), sds((K,), "bool", one))
    else:
        program = eng._build_block(
            T, steps, [eng._shard_mix_op(t, None) if mixing else None
                       for t in range(T)])
        tail = (sds((T, K, K), "float32", one), sds((T, K), "bool", one),
                sds((T,), "int32", one))
    compiled = program.lower(
        *head, *tail, sds(key.shape, key.dtype, one)).compile()
    permutes = re.findall(r"\bcollective-permute(?:-start)?\(",
                          compiled.as_text())
    assert bool(permutes) == mixing, len(permutes)
    mem = compiled.memory_analysis()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state)) / K
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    assert peak < 15.75 * 2 ** 30, peak
