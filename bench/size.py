"""Compile a cell's round-block for a described TPU v5e and print its
``memory_analysis``: what the program will hold on each chip, read before
any chip time is spent. Nothing is allocated; run it on a machine with the
TPU compiler installed and no chip attached:

    JAX_PLATFORMS=cpu python3 bench/size.py phi3.fed4.dp [more cells]

A one-chip cell is compiled for one chip of a described ``v5e:2x2``, a
four-chip cell for all four, with its state placed one client per chip.
Where JAX finds a TPU, the same program is compiled for the attached chips
instead, so the two readings can be set side by side.
"""
from __future__ import annotations

import importlib
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
GIB = 2 ** 30


def size(cell_name: str) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    import repro.kernels
    from bench import harness

    repro.kernels.default_interpret = lambda: False  # compile for Mosaic
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, ROOT, cell_name)
    if jax.devices()[0].platform == "tpu":
        devices = jax.devices()[:cell.chips]
    else:
        devices = list(topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2").devices)[:cell.chips]
    family = importlib.import_module(f"bench.family_{cell.config['family']}")
    fed = family.build(cell.config, cell.traffic, 0, devices)
    eng, K = fed.engine, fed.fed["clients"]
    R, steps = fed.rounds_per_block, fed.local_steps
    if eng.backend == "shard_map":
        place = NamedSharding(fed.mesh, PartitionSpec(eng.axis))
        ops = [eng._shard_mix_op(t, None) for t in range(R)]
    else:
        place = SingleDeviceSharding(devices[0])
        ops = eng._mix_matmul_op()
    one = SingleDeviceSharding(devices[0]) if eng.backend != "shard_map" \
        else NamedSharding(fed.mesh, PartitionSpec())
    block = eng._build_block(R, steps, ops)

    def sds(shape, dtype, sharding=place):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)

    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    state = jax.tree_util.tree_map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(eng.init_states, key))
    data = [sds(s, d) for s, d in fed.data_shapes()]
    data = data[0] if len(data) == 1 else tuple(data)
    compiled = block.lower(
        state, data, sds((K,), "int32"), sds((K,), "int32", one),
        sds((R, K, K), "float32", one), sds((R, K), "bool", one),
        sds((R,), "int32", one), sds(key.shape, key.dtype, one)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree_util.tree_leaves(state))
    out = {
        "cell": cell_name, "chips": cell.chips,
        "compiled_for": devices[0].device_kind,
        "state_gib_per_chip": state_bytes / cell.chips / GIB,
        "argument_gib": mem.argument_size_in_bytes / GIB,
        "temp_gib": mem.temp_size_in_bytes / GIB,
        "output_gib": mem.output_size_in_bytes / GIB,
        "alias_gib": mem.alias_size_in_bytes / GIB,
        "peak_gib": (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                     + mem.output_size_in_bytes - mem.alias_size_in_bytes)
        / GIB,
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "collective_permutes": text.count("collective-permute-start"),
    }
    return out


def main(argv) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    for name in argv:
        print(json.dumps(size(name)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
