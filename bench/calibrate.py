"""Readings the correctness limits are set from, taken on the chip at a
cell's own size, in one process:

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \\
        [--control-seeds 1 2 3] [--fault-seeds 1 2 3] [--faults token ...]

For every seed, the program is driven from the seed through the cell's
first ``harness.CHECK_STEPS`` round-blocks exactly as a benchmark run's
set-up drives it, and compared with the reference: the lower readings. For every control seed
the reference computed in bfloat16 is put in the program's place: the
control's readings. For every fault seed each named fault of
``bench/faults.py`` is planted in the program. One JSON line per reading.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--dump", default=None,
                    help="a directory to write every raw reading to, as "
                         "<kind>.<seed>.json")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    import jax.numpy as jnp

    from bench import faults, harness
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("calibrate: JAX found no TPU", file=sys.stderr)
        return 3
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, ROOT, args.workload)
    family = importlib.import_module(f"bench.family_{cell.config['family']}")
    devices = jax.devices()[:cell.chips]
    steps = harness.CHECK_STEPS
    refs = {}

    def build(seed, fault=None):
        fed = family.build(cell.config, cell.traffic, seed, devices)
        if fault:
            faults.plant(fed, fault)
        fed.setup()
        return fed

    def dump(kind, seed, raw):
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            (Path(args.dump) / f"{kind}.{seed}.json").write_text(json.dumps(
                jax.tree_util.tree_map(lambda a: a.tolist(), raw)))

    def emit(kind, seed, raw, t0):
        dump(kind, seed, raw)
        print(json.dumps(dict(kind=kind, seed=seed, seconds=time.perf_counter()
                              - t0, **harness.compare(raw, reference(seed)))),
              flush=True)

    def reference(seed):
        if seed not in refs:
            refs[seed] = build_ref(seed).follow(steps)
            dump("reference", seed, refs[seed])
        return refs[seed]

    def build_ref(seed):
        return family.build(cell.config, cell.traffic, seed, devices)

    for seed in sorted(set(args.seeds) | set(args.fault_seeds)):
        runs = ([None] if seed in args.seeds else []) + (
            args.faults if seed in args.fault_seeds else [])
        for fault in runs:
            t0 = time.perf_counter()
            fed = build(seed, fault)
            prog = harness.first_blocks(fed, steps)
            fed.free()
            del fed
            gc.collect()
            emit(fault or "program", seed, prog, t0)
    for seed in args.control_seeds:
        t0 = time.perf_counter()
        emit("control", seed, build_ref(seed).follow(steps, jnp.bfloat16), t0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
