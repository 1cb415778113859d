import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove the distribution config is coherent without
real hardware.

For every (architecture × input shape × mesh) combination this lowers and
compiles the corresponding step program against ``jax.ShapeDtypeStruct``
stand-ins (no allocation), prints ``memory_analysis()`` /
``cost_analysis()``, parses the post-SPMD HLO for collective traffic, and
derives the three roofline terms (compute / memory / collective) against
TPU v5e constants. Results are written as JSON artifacts consumed by
``benchmarks/roofline.py`` and EXPERIMENTS.md.

The two lines above MUST stay the very first statements of this module:
jax locks the device count at first initialization, and the dry-run needs
512 placeholder host devices to build the production meshes. They are set
here and ONLY here — tests and benchmarks keep seeing one CPU device.

Usage::

    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
    python -m repro.launch.dryrun --all --mesh both --out results/dryrun
    python -m repro.launch.dryrun --arch jamba-1.5-large-398b --shape long_500k \
        --mesh single --tag kvq8 --kv-cache-dtype bfloat16
    python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k \
        --mesh multi --program hier_block --clients-per-shard 4

``--program hier_block`` lowers the engine's ``backend="hier"`` round-block
on the two-level mesh: one shard of ``--clients-per-shard`` stacked clients
per pod, intra-shard PushSum as a local block matmul, cross-shard edges as
at most two ppermutes along "pod" per round.
"""
# NOTE: no ``from __future__ import annotations`` here — the XLA_FLAGS lines
# above must be the first statements of the module, which rules it out.

import argparse
import json
import sys
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import INPUT_SHAPES, get_config, list_archs
from ..configs.base import DPConfig, InputShape, ModelConfig, ProxyFLConfig
from ..configs.registry import proxy_of
from .mesh import TPU_V5E, make_production_mesh
from .sharding import named
from .steps import (
    StepOptions,
    input_specs,
    make_decode_step,
    make_fl_round_step,
    make_hier_round_block_step,
    make_prefill_step,
    make_round_block_step,
    make_train_step,
    serve_shardings,
    serve_state_shapes,
    train_shardings,
    train_state_shapes,
)

#: rounds fused into one program by ``--program round_block`` /
#: ``hier_block`` (the engine's round-block unit; static — each round's
#: ppermute schedule is baked in)
BLOCK_ROUNDS = 4

#: clients stacked per pod by ``--program hier_block`` (the two-level mesh:
#: n_shards = pod count, clients_per_shard vmapped within each pod;
#: override with --clients-per-shard)
CLIENTS_PER_SHARD = 4

# Architectures with sub-quadratic context handling run long_500k; pure
# full-attention architectures skip it (DESIGN.md "long_500k skip decisions").
LONG_CONTEXT_OK = {
    "falcon-mamba-7b",       # SSM: O(1) state
    "jamba-1.5-large-398b",  # hybrid: KV only on every 8th layer
    "gemma3-4b",             # 5:1 sliding-window
    "qwen2-7b-swa",          # beyond-paper dense->SWA override
}

from .hlo_cost import collective_wire_bytes, step_cost


def _cost_dict(compiled) -> Dict[str, float]:
    try:
        c = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(c, (list, tuple)):
        c = c[0] if c else {}
    return dict(c) if c else {}


def _memory_dict(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes")
    out = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _spec_shard_count(spec: P, mesh) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n = 1
    for entry in spec:
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        for a in axes:
            n *= sizes[a]
    return n


def sharded_bytes_per_device(shapes_tree, specs_tree, mesh) -> int:
    """Analytic per-device bytes of a sharded pytree of ShapeDtypeStructs."""
    total = 0
    flat_s, _ = jax.tree_util.tree_flatten(shapes_tree)
    flat_p, _ = jax.tree_util.tree_flatten(
        specs_tree, is_leaf=lambda x: isinstance(x, P))
    assert len(flat_s) == len(flat_p), (len(flat_s), len(flat_p))
    for sds, spec in zip(flat_s, flat_p):
        nbytes = int(np.prod(sds.shape)) * jnp.dtype(sds.dtype).itemsize if sds.shape else jnp.dtype(sds.dtype).itemsize
        total += nbytes // _spec_shard_count(spec, mesh)
    return total


# ---------------------------------------------------------------------------
# roofline


def roofline(flops_dev: float, bytes_dev: float, coll: Dict[str, Any],
             hw=TPU_V5E) -> Dict[str, Any]:
    """Three-term roofline, all in seconds-per-step on ONE chip (the SPMD
    program is per-device, so per-device terms ARE the global-step terms)."""
    coll_total = coll["total_wire_bytes"]
    t_compute = flops_dev / hw["peak_flops_bf16"]
    t_memory = bytes_dev / hw["hbm_bandwidth"]
    t_collective = coll_total / hw["ici_bandwidth"]
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_collective}
    dominant = max(terms, key=terms.get)
    return {**terms, "dominant": dominant.replace("_s", ""),
            "collective_bytes_per_device": coll_total,
            "collective_breakdown": coll["wire_bytes"],
            "collective_op_counts": coll["op_counts"]}


def model_flops(cfg: ModelConfig, shape: InputShape, proxy: Optional[ModelConfig],
                fl_dp: bool = True) -> float:
    """Useful-work FLOPs for one step: 6·N_active·tokens for training (the
    ProxyFL DML step trains private AND proxy, plus each model runs one
    extra peer forward → private 6+2, proxy 6+2), 2·N_active·tokens for
    inference."""
    counts = cfg.param_counts()
    n_act = counts["active"]
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        f = 8.0 * n_act * toks  # 6 (fwd+bwd) + 2 (peer forward for proxy's KL)
        if proxy is not None:
            n_px = proxy.param_counts()["active"]
            f += 8.0 * n_px * toks
        return f
    toks = shape.global_batch * (shape.seq_len if shape.kind == "prefill" else 1)
    return 2.0 * n_act * toks


# ---------------------------------------------------------------------------
# one dry-run combination


#: dry-run defaults: activation constraints ON (we are on a mesh), DP chunk
#: = data-axis size so per-example grads divide across data rows.
DRYRUN_OPTS = StepOptions(shard_acts=True, dp_chunk=16)


def run_one(arch: str, shape_name: str, mesh_kind: str, *,
            program: str = "auto", opts: StepOptions = DRYRUN_OPTS,
            clients_per_shard: int = CLIENTS_PER_SHARD,
            tag: str = "", verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    chips = int(np.prod(mesh.devices.shape))
    if program == "auto":
        program = {"train": "train", "prefill": "prefill",
                   "decode": "decode"}[shape.kind]

    if shape_name == "long_500k" and arch not in LONG_CONTEXT_OK:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "program": program, "status": "skipped",
                "reason": "pure full-attention architecture (DESIGN.md skip)"}

    fl = ProxyFLConfig(dp=DPConfig(enabled=True))
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    t0 = time.time()

    if program in ("train", "fl_round", "round_block", "hier_block"):
        proxy = proxy_of(cfg)
        pods = mesh.shape.get("pod", 0)
        if program == "hier_block":
            if not pods:
                raise ValueError(
                    "--program hier_block needs the two-level (multi-pod) "
                    "mesh — run with --mesh multi")
            # two-level cohort: one SHARD per pod, clients_per_shard
            # clients vmapped within it
            n_clients = pods * clients_per_shard
        else:
            n_clients = pods if program in ("fl_round", "round_block") else 0
        state_sds = train_state_shapes(cfg, proxy, fl, opts)
        if n_clients:
            state_sds = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n_clients,) + s.shape, s.dtype),
                state_sds)
            key_sds = jax.ShapeDtypeStruct((n_clients, 2), jnp.uint32)
        batch_sds = input_specs(cfg, shape, n_clients=n_clients)
        state_spec, batch_spec, modes = train_shardings(
            mesh, state_sds, batch_sds, n_clients=n_clients,
            expert_parallel=opts.expert_parallel)
        if program == "fl_round":
            step = make_fl_round_step(cfg, proxy, fl, mesh, n_clients, opts,
                                      round_t=0)
            metrics_spec = {"private_loss": P("pod"), "proxy_loss": P("pod")}
        elif program == "round_block":
            step = make_round_block_step(cfg, proxy, fl, mesh, n_clients,
                                         opts, n_rounds=BLOCK_ROUNDS)
            # metrics stacked [n_rounds, K]: round dim replicated, K on pod
            metrics_spec = {"private_loss": P(None, "pod"),
                            "proxy_loss": P(None, "pod")}
        elif program == "hier_block":
            step = make_hier_round_block_step(
                cfg, proxy, fl, mesh, pods, clients_per_shard, opts,
                n_rounds=BLOCK_ROUNDS)
            # stacked [n_rounds, K]: K = pods·clients_per_shard, contiguous
            # shard blocks of clients_per_shard live on each pod
            metrics_spec = {"private_loss": P(None, "pod"),
                            "proxy_loss": P(None, "pod")}
        else:
            step = make_train_step(cfg, proxy, fl, opts)
            metrics_spec = {"private_loss": P(), "proxy_loss": P()}
        jitted = jax.jit(
            step,
            in_shardings=(named(state_spec, mesh), named(batch_spec, mesh),
                          NamedSharding(mesh, P() if not n_clients else P("pod"))),
            out_shardings=(named(state_spec, mesh), named(metrics_spec, mesh)),
            donate_argnums=(0,),  # in-place params/opt update (no double buffer)
        )
        args = (state_sds, batch_sds, key_sds)
        arg_bytes_dev = (sharded_bytes_per_device(state_sds, state_spec, mesh)
                         + sharded_bytes_per_device(batch_sds, batch_spec, mesh))
        mf = model_flops(cfg, shape, proxy)
        if program in ("round_block", "hier_block"):
            mf *= BLOCK_ROUNDS  # the program does n_rounds rounds of work
        if program == "hier_block":
            # n_clients DML steps per round, not one per pod
            mf *= n_clients / max(1, pods)
    if program not in ("train", "fl_round", "round_block", "hier_block"):
        modes = None
        state_sds = serve_state_shapes(cfg, shape)
        batch_sds = input_specs(cfg, shape)
        state_spec, batch_spec = serve_shardings(
            mesh, state_sds, batch_sds, expert_parallel=opts.expert_parallel,
            serve_2d=opts.serve_2d)
        maker = make_prefill_step if program == "prefill" else make_decode_step
        step = maker(cfg, opts)
        logits_spec = P(None, "model") if cfg.modality != "audio" else P()
        jitted = jax.jit(
            step,
            in_shardings=(named(state_spec, mesh), named(batch_spec, mesh)),
            out_shardings=(named(state_spec, mesh), None),
            donate_argnums=(0,),  # in-place KV-cache update
        )
        args = (state_sds, batch_sds)
        arg_bytes_dev = (sharded_bytes_per_device(state_sds, state_spec, mesh)
                         + sharded_bytes_per_device(batch_sds, batch_spec, mesh))
        mf = model_flops(cfg, shape, None)

    with jax.set_mesh(mesh):
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        # global, trip-count-corrected cost from the traced jaxpr (XLA's
        # cost_analysis counts while bodies once — useless for scan stacks)
        jc = step_cost(step, *args)
    cost = _cost_dict(compiled)
    memory = _memory_dict(compiled)
    coll = collective_wire_bytes(compiled.as_text())
    flops_dev = jc["flops"] / chips
    bytes_dev = jc["bytes"] / chips
    rl = roofline(flops_dev, bytes_dev, coll)
    result = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "program": program, "tag": tag, "status": "ok",
        "chips": chips, "sharding_modes": modes,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_global": jc["flops"],
        "bytes_global": jc["bytes"],
        "flops_per_device": flops_dev,
        "bytes_per_device": bytes_dev,
        "xla_cost_analysis_raw": {
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_per_device": float(cost.get("bytes accessed", 0.0)),
            "note": "while bodies counted once by XLA; see flops_global",
        },
        "argument_bytes_per_device": arg_bytes_dev,
        "memory_analysis": memory,
        "roofline": rl,
        "model_flops": mf,
        "useful_flops_ratio": (mf / jc["flops"]) if jc["flops"] else None,
        "params_total": cfg.param_counts()["total"],
        "params_active": cfg.param_counts()["active"],
        "opts": {k: getattr(opts, k) for k in
                 ("remat", "accum", "dp_chunk", "kv_chunk", "mamba_chunk",
                  "expert_parallel", "moment_dtype", "serve_2d")},
    }
    if verbose:
        print(f"[dryrun] {arch} × {shape_name} × {mesh_kind} × {program}"
              f"{' × ' + tag if tag else ''}")
        print(f"  lower {t_lower:.1f}s  compile {t_compile:.1f}s  chips {chips}")
        print(f"  memory_analysis: {memory}")
        print(f"  jaxpr cost: flops/dev {flops_dev:.3e}  bytes/dev {bytes_dev:.3e}")
        print(f"  collective wire bytes/dev: { {k: f'{v:.3e}' for k, v in rl['collective_breakdown'].items()} }")
        print(f"  roofline: compute {rl['compute_s']*1e3:.2f}ms  memory "
              f"{rl['memory_s']*1e3:.2f}ms  collective {rl['collective_s']*1e3:.2f}ms"
              f"  → {rl['dominant']}-bound")
        print(f"  MODEL_FLOPS {mf:.3e}  useful/jaxpr {result['useful_flops_ratio']:.3f}")
    return result


# ---------------------------------------------------------------------------
# CLI


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs())
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"), default="single")
    ap.add_argument("--program", default="auto",
                    choices=("auto", "train", "fl_round", "round_block",
                             "hier_block", "prefill", "decode"))
    ap.add_argument("--clients-per-shard", type=int,
                    default=CLIENTS_PER_SHARD,
                    help="clients stacked per pod for --program hier_block "
                         "(the two-level mesh: n_shards = pod count)")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × shape) for the chosen mesh(es)")
    ap.add_argument("--out", default="results/dryrun", help="JSON output dir")
    ap.add_argument("--tag", default="", help="perf-iteration tag")
    # StepOptions overrides (the §Perf levers)
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--accum", type=int)
    ap.add_argument("--dp-chunk", type=int)
    ap.add_argument("--kv-chunk", type=int)
    ap.add_argument("--mamba-chunk", type=int)
    ap.add_argument("--expert-parallel", action="store_true")
    ap.add_argument("--serve-2d", action="store_true")
    ap.add_argument("--moment-dtype")
    args = ap.parse_args(argv)

    opts = DRYRUN_OPTS
    kw = {}
    if args.no_remat:
        kw["remat"] = False
    for name in ("accum", "dp_chunk", "kv_chunk", "mamba_chunk", "moment_dtype"):
        v = getattr(args, name)
        if v is not None:
            kw[name] = v
    if args.expert_parallel:
        kw["expert_parallel"] = True
    if args.serve_2d:
        kw["serve_2d"] = True
    if kw:
        opts = opts.with_(**kw)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    combos = []
    if args.all:
        for a in list_archs():
            for s in sorted(INPUT_SHAPES):
                for m in meshes:
                    combos.append((a, s, m))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required unless --all")
        combos = [(args.arch, args.shape, m) for m in meshes]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for a, s, m in combos:
        try:
            res = run_one(a, s, m, program=args.program, opts=opts,
                          clients_per_shard=args.clients_per_shard,
                          tag=args.tag)
        except Exception as e:  # a dry-run failure is a bug in the system
            failures += 1
            res = {"arch": a, "shape": s, "mesh": m, "status": "FAILED",
                   "error": f"{type(e).__name__}: {e}"}
            print(f"[dryrun] FAILED {a} × {s} × {m}: {e}", file=sys.stderr)
        fname = f"{a}__{s}__{m}__{args.program}"
        if args.tag:
            fname += f"__{args.tag}"
        with open(os.path.join(args.out, fname + ".json"), "w") as f:
            json.dump(res, f, indent=1)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
