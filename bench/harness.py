"""The benchmark harness: everything a run does after ``run.py`` has found
the program.

A cell is found by its name in ``BENCHMARK.json``: its configuration file,
its traffic file ``bench/traffic/<traffic>.json``, its limits
``bench/limits/<cell>.json`` and the readers ``bench/metrics/<metric>.py``
of its per-layer metrics. Nothing here names a cell, a configuration or a
metric.

A run: set-up (the program's federation built from the seed, then the first
``CHECK_STEPS`` round-blocks through the window's own call, which compiles
every shape and records what the check compares, and one block-edge
evaluation); the window (whole round-blocks with their block-edge host work
until ``--seconds`` have passed); with ``--trace 1`` a profiler trace of the
window's first ``TRACE_BLOCKS`` blocks; then, with the program's state
freed, the plain reference over the same first blocks, and the comparison.
"""
from __future__ import annotations

import contextlib
import gc
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from . import trace as tr

GIB = 2 ** 30
#: round-blocks set-up drives and the reference follows
CHECK_STEPS = 2
#: window blocks a ``--trace 1`` run traces
TRACE_BLOCKS = 2


class NoChip(RuntimeError):
    """The run cannot measure: no accelerator, too few chips, or a device
    the peaks table does not know."""


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    limits: Dict[str, float]
    end_to_end: List[Dict]
    per_layer: List[Dict]


def load_cell(spec: Dict, root: Path, name: str) -> Cell:
    """The cell ``name`` of a parsed ``BENCHMARK.json`` whose paths are
    relative to ``root``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    bench = root / spec["paths"][0]
    traffic = json.loads((bench / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    limits = json.loads((bench / "limits" / f"{name}.json").read_text())
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in names)]
    return Cell(name, int(w["chips"]), config, traffic, limits, e2e,
                per_layer)


def load_reader(bench: Path, metric: str) -> Callable:
    """``read(run)`` of ``bench/metrics/<metric>.py``."""
    path = bench / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclass
class RunRecord:
    """What a per-layer metric's reader may read from a traced run."""

    chips: int
    peaks: Optional[Dict]
    round_flops: float
    rounds: int                      # rounds in the traced window
    trace: tr.Trace
    lo: float                        # traced window, profiler clock (ns)
    hi: float
    run_ns: float                    # engine call + wait, in the window

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def device_ops(self):
        return list(self.trace.devices.values())

    def busy_s(self) -> float:
        ops = self.device_ops()
        return sum(tr.busy_ns(o, self.lo, self.hi) for o in ops) / (
            1e9 * max(1, len(ops)))


class Compiles:
    """Counts backend compilations (persistent-cache loads included)."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


@contextlib.contextmanager
def span(name: str):
    """A harness host span: a profiler annotation named ``bench.<name>``."""
    import jax

    with jax.profiler.TraceAnnotation(tr.SPAN_PREFIX + name):
        yield


def gaps(prog: np.ndarray, ref: np.ndarray,
         floor: Optional[float] = None) -> np.ndarray:
    """``|prog - ref| / max(|ref|, floor)``, entry by entry."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    den = np.abs(ref) if floor is None else np.maximum(np.abs(ref), floor)
    den = np.where(den > 0, den, 1.0)
    return np.abs(prog - ref) / den


def gap(prog: np.ndarray, ref: np.ndarray, floor: Optional[float] = None,
        keep: Optional[np.ndarray] = None) -> float:
    """Worst of :func:`gaps` over the kept entries."""
    g = gaps(prog, ref, floor)
    if keep is not None:
        g = g[keep]
    if g.size == 0:
        return float("nan")
    return float(np.max(g)) if np.isfinite(g).all() else float("inf")


def median_gap(prog: np.ndarray, ref: np.ndarray, floor: float) -> float:
    """Median of :func:`gaps` over all entries."""
    g = gaps(prog, ref, floor)
    return float(np.median(g)) if np.isfinite(g).all() else float("inf")


def compare(prog: Dict, ref: Dict) -> Dict[str, float]:
    """The numbers the check may compare, for each role (private, proxy):
    the worst relative gap of any round's loss; of any weight's
    first-moment norm after the first block, against that norm or the
    median one, whichever is larger (``moment_gap``), and the median of
    those gaps over weights and clients (``moment_median_gap``); and of any
    weight's change after the last block, likewise, leaving out weights
    whose reference first moment is under a thousandth of the median.
    ``loss_gap``, ``moment_gap`` and ``change_gap`` are the worst of the
    two roles."""
    out = {}
    for i, role in enumerate(ref["moment"]):
        out[f"{role}_loss_gap"] = gap(prog["loss"][..., i],
                                      ref["loss"][..., i])
        m_ref = ref["moment"][role]
        med = float(np.median(m_ref))
        out[f"{role}_moment_gap"] = gap(prog["moment"][role], m_ref, med)
        out[f"{role}_moment_median_gap"] = median_gap(prog["moment"][role],
                                                      m_ref, med)
        c_ref = ref["change"][role]
        out[f"{role}_change_gap"] = gap(prog["change"][role], c_ref,
                                        float(np.median(c_ref)),
                                        m_ref >= 1e-3 * med)
    for kind in ("loss", "moment", "change"):
        out[f"{kind}_gap"] = max(out[f"{r}_{kind}_gap"] for r in ref["moment"])
    return out


def first_blocks(fed, steps: int) -> Dict:
    """Drive ``fed`` from its seed through its first ``steps`` round-blocks
    by the window's own call, with one block-edge evaluation after the
    first, and record what the check compares: every round's losses, the
    first-moment norms after the first block and the weights' change after
    the last."""
    prog: Dict = {"loss": []}
    for i in range(steps):
        metrics = fed.run_block()
        prog["loss"].append(fed.losses(metrics))
        if i == 0:
            prog["moment"] = fed.moment_norms()
            fed.edge(metrics)
    prog["loss"] = np.concatenate(prog["loss"])
    prog["change"] = fed.change_norms()
    return prog


def run_cell(spec: Dict, root: Path, name: str, seed: int, seconds: float,
             trace: bool, t_start: float, allow_cpu: bool = False,
             log: Callable = print) -> Dict:
    """One run of the cell ``name``; returns the result line's object."""
    import jax

    cell = load_cell(spec, root, name)
    bench = root / spec["paths"][0]
    devices = jax.devices()
    platform = devices[0].platform
    if not allow_cpu:
        if platform != "tpu":
            raise NoChip(f"JAX found no TPU (platform {platform!r})")
        if len(devices) < cell.chips:
            raise NoChip(f"the cell needs {cell.chips} chips, JAX found "
                         f"{len(devices)}")
    peaks_all = json.loads((bench / "peaks.json").read_text())
    kind = devices[0].device_kind
    peaks = peaks_all.get(kind)
    if peaks is None and not allow_cpu:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    devices = devices[:cell.chips]

    cache = None
    if not allow_cpu:
        from repro.launch.compile_cache import use_compile_cache

        cache = use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = Compiles()
    family = importlib.import_module(
        f"{__package__}.family_{cell.config['family']}")
    fed = family.build(cell.config, cell.traffic, seed, devices)
    fed.setup()
    prog = first_blocks(fed, CHECK_STEPS)
    setup_s = time.perf_counter() - t_start
    log(f"[bench] {name}: set-up {setup_s:.3f} s, compile cache {cache}")

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    compiled_before = compiles.count
    rounds = failed = blocks = 0
    traced_rounds = 0
    if trace:
        jax.profiler.start_trace(trace_dir)
    tracing = trace
    t0 = time.perf_counter()
    while True:
        with span("block"):
            with span("run"):
                metrics = fed.run_block()
            with span("edge"):
                fed.edge(metrics)
                failed += fed.rounds_failed(metrics)
        rounds += fed.rounds_per_block
        blocks += 1
        if tracing and blocks == TRACE_BLOCKS:
            jax.profiler.stop_trace()
            tracing, traced_rounds = False, rounds
        if time.perf_counter() - t0 >= seconds:
            break
    window_s = time.perf_counter() - t0
    if tracing:
        jax.profiler.stop_trace()
        traced_rounds = rounds
    in_window = compiles.count - compiled_before
    log(f"[bench] {name}: compiles inside the window: {in_window}")
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    round_flops = fed.round_flops()
    fed.free()
    gc.collect()

    t_ref = time.perf_counter()
    ref = fed.follow(CHECK_STEPS)
    readings = compare(prog, ref)
    log(f"[bench] {name}: reference {time.perf_counter() - t_ref:.3f} s")
    for k in sorted(set(readings) - set(cell.limits)):
        log(f"[bench] not compared: {k} = {readings[k]!r}")
    checks = {k: readings[k] for k in cell.limits}
    correct = all(np.isfinite(v) and v <= cell.limits[k]
                  for k, v in checks.items())

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak}
    out: Dict = {"correct": bool(correct), "attempted": rounds,
                 "failed": failed}
    if not trace:
        values = {"setup_s": setup_s, "round_s": window_s / rounds,
                  "peak_hbm_gib": peak / GIB}
        out["metrics"] = {m["name"]: {"value": values[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end}
    else:
        rec = traced_record(trace_dir, cell, peaks, round_flops,
                            traced_rounds)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = rec.busy_s()
        device["window_s"] = rec.window_s
        out["metrics"] = {}
        for m in cell.per_layer:
            v = load_reader(bench, m["name"])(rec)
            if v is not None:
                out["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        out["breakdown"] = breakdown(rec)
        log(f"[bench] {name}: traced {traced_rounds} rounds in "
            f"{rec.window_s:.3f} s; untraced window {window_s:.3f} s for "
            f"{rounds} rounds")
    out["device"] = device
    out["checks"] = {k: {"value": v, "limit": cell.limits[k]}
                     for k, v in checks.items()}
    return out


def traced_record(trace_dir: str, cell: Cell, peaks, round_flops,
                  rounds: int) -> RunRecord:
    t = tr.load(trace_dir)
    blocks = [s for s in t.spans if s[0] == tr.SPAN_PREFIX + "block"]
    runs = [s for s in t.spans if s[0] == tr.SPAN_PREFIX + "run"]
    lo, hi = blocks[0][1], blocks[-1][2]
    return RunRecord(chips=cell.chips, peaks=peaks, round_flops=round_flops,
                     rounds=rounds, trace=t, lo=lo, hi=hi,
                     run_ns=sum(e - s for _, s, e in runs))


def breakdown(rec: RunRecord) -> Dict:
    """The ten device ops with the most self time (seconds per chip) and
    the ten longest idle gaps, named by the harness span open in each."""
    ops_all = rec.device_ops()
    totals: Dict[str, float] = {}
    for ops in ops_all:
        for k, v in tr.self_times(ops).items():
            totals[k] = totals.get(k, 0.0) + v / max(1, len(ops_all))
    gaps: List = []
    for ops in ops_all:
        gaps += tr.attribute_gaps(ops, rec.trace.spans, rec.lo, rec.hi)
    top = sorted(totals.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in
                          sorted(gaps, key=lambda g: -g[1])[:10]]}


def main(argv: List[str], root: Path, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = json.loads((root / "BENCHMARK.json").read_text())

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        out = run_cell(spec, root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start, log=log)
    except NoChip as e:
        log(f"[bench] refused: {e}")
        return 3
    for k, v in out["checks"].items():
        log(f"[bench] check {k} = {v['value']!r} (limit {v['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0
