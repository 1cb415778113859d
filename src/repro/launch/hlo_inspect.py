import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""HLO inspector — the dry-run "profiler".

Compiles one (arch × shape × mesh) combination exactly like dryrun.py and
prints (a) collective wire bytes aggregated by op_name metadata (with
while-loop trip-count multipliers), (b) the largest live tensors. This is
what the §Perf hypothesis loop reads instead of a wall-clock profile.

    python -m repro.launch.hlo_inspect --arch jamba-1.5-large-398b \
        --shape train_4k --mesh single [--expert-parallel ...]
"""

import argparse
import re
from collections import Counter

import numpy as np

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
          "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8}


def analyze(txt: str, top: int = 20):
    from .hlo_cost import (_BODY, _CALLS, _COLL_LINE, _COMP_HEADER,
                           _CONDITION, _TRIP, _group_size, _result_bytes,
                           _wire)
    comps = {}
    entry = None
    cur = None
    for line in txt.splitlines():
        h = _COMP_HEADER.match(line)
        if h:
            cur = h.group(2)
            comps[cur] = {"c": [], "e": []}
            if h.group(1):
                entry = cur
            continue
        if cur is None:
            continue
        m = _COLL_LINE.search(line)
        if m:
            d, dims, kind = m.groups()
            md = re.search(r'op_name="([^"]*)"', line)
            comps[cur]["c"].append(
                (kind, _result_bytes(d, dims), _group_size(line),
                 (md.group(1)[:90] if md else line.strip()[:90])))
        if re.search(r"\bwhile\(", line):
            t = _TRIP.search(line)
            n = int(t.group(1)) if t else 1
            b = _BODY.search(line)
            c2 = _CONDITION.search(line)
            if b:
                comps[cur]["e"].append((b.group(1), n))
            if c2:
                comps[cur]["e"].append((c2.group(1), n + 1))
        else:
            for cal in _CALLS.findall(line):
                comps[cur]["e"].append((cal, 1))
    mult = {}
    st = [(entry, 1.0)]
    while st:
        nm, m_ = st.pop()
        mult[nm] = mult.get(nm, 0.0) + m_
        for cal, n in comps.get(nm, {}).get("e", []):
            if cal in comps:
                st.append((cal, m_ * n))
    agg = Counter()
    for nm, d in comps.items():
        for kind, r, g, op in d["c"]:
            agg[(kind, op)] += mult.get(nm, 0) * _wire(kind, r, g)
    print("=== collective wire bytes by op (trip-count weighted) ===")
    for (kind, op), w in agg.most_common(top):
        print(f"{w/2**30:9.2f}GiB {kind:18s} {op}")

    pat = re.compile(r"= (f32|bf16|s32|f16|u32)\[([0-9,]+)\]")
    seen = []
    for line in txt.splitlines():
        m = pat.search(line)
        if m:
            d, dims = m.groups()
            n = int(np.prod([int(x) for x in dims.split(",")])) * _BYTES[d]
            if n > 2 ** 30:
                seen.append((n, line.strip()[:150]))
    seen.sort(key=lambda t: -t[0])
    print("=== tensors >1GiB (per-device) ===")
    done = set()
    for n, l in seen:
        md = re.search(r'op_name="([^"]*)"', l)
        key = md.group(1)[:70] if md else l.split("(")[0][-60:]
        if key in done:
            continue
        done.add(key)
        print(f"{n/2**30:7.2f}GiB {key}")
        if len(done) >= top:
            break


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..configs import INPUT_SHAPES, get_config, list_archs
    from ..configs.base import DPConfig, ProxyFLConfig
    from ..configs.registry import proxy_of
    from .mesh import make_production_mesh
    from .sharding import named
    from .steps import (input_specs, make_decode_step,
                        make_prefill_step, make_train_step, serve_shardings,
                        serve_state_shapes, train_shardings,
                        train_state_shapes)
    from .dryrun import DRYRUN_OPTS

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list_archs(), required=True)
    ap.add_argument("--shape", choices=sorted(INPUT_SHAPES), required=True)
    ap.add_argument("--mesh", choices=("single", "multi"), default="single")
    ap.add_argument("--top", type=int, default=20)
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

    cfg = get_config(args.arch)
    shape = INPUT_SHAPES[args.shape]
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    fl = ProxyFLConfig(dp=DPConfig(enabled=True))
    if shape.kind == "train":
        proxy = proxy_of(cfg)
        state_sds = train_state_shapes(cfg, proxy, fl, opts)
        batch_sds = input_specs(cfg, shape)
        state_spec, batch_spec, _ = train_shardings(mesh, state_sds, batch_sds,
                                                    expert_parallel=opts.expert_parallel)
        step = make_train_step(cfg, proxy, fl, opts)
        jitted = jax.jit(step, in_shardings=(
            named(state_spec, mesh), named(batch_spec, mesh),
            NamedSharding(mesh, P())),
            out_shardings=(named(state_spec, mesh),
                           named({"private_loss": P(), "proxy_loss": P()}, mesh)),
            donate_argnums=(0,))
        args_ = (state_sds, batch_sds, jax.ShapeDtypeStruct((2,), jnp.uint32))
    else:
        state_sds = serve_state_shapes(cfg, shape)
        batch_sds = input_specs(cfg, shape)
        state_spec, batch_spec = serve_shardings(
            mesh, state_sds, batch_sds, expert_parallel=opts.expert_parallel,
            serve_2d=opts.serve_2d)
        maker = make_prefill_step if shape.kind == "prefill" else make_decode_step
        jitted = jax.jit(maker(cfg, opts), in_shardings=(
            named(state_spec, mesh), named(batch_spec, mesh)),
            out_shardings=(named(state_spec, mesh), None), donate_argnums=(0,))
        args_ = (state_sds, batch_sds)

    with jax.set_mesh(mesh):
        txt = jitted.lower(*args_).compile().as_text()
    analyze(txt, top=args.top)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
