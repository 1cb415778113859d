"""A benchmark root for the CPU tests: the real harness, metric readers and
peaks table, with a cell small enough for a test run: ``tiny.lm`` runs the
LM family and is held to the limits of ``phi3.fed4.dp``."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

DATA = Path(__file__).resolve().parent / "data"
#: tiny cell -> (configuration, traffic, the cell whose limits hold)
CELLS = {"tiny.lm": ("tiny-lm", "tiny_lm", "phi3.fed4.dp")}


def make_root(tmp: Path):
    """A root with ``bench/`` holding the tiny cells' files; returns the
    root and its parsed benchmark spec."""
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp / "bench"
    for d in ("configs", "traffic", "limits"):
        (b / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(ROOT / "bench" / "metrics", b / "metrics")
    shutil.copy(ROOT / "bench" / "peaks.json", b / "peaks.json")
    spec = {"paths": ["bench"], "configs": [], "workloads": [],
            "end_to_end": real["end_to_end"], "per_layer": []}
    for cell, (config, traffic, stands_for) in CELLS.items():
        shutil.copy(DATA / "tiny" / f"{config}.json", b / "configs")
        shutil.copy(DATA / "tiny" / f"{traffic}.json", b / "traffic")
        shutil.copy(ROOT / "bench" / "limits" / f"{stands_for}.json",
                    b / "limits" / f"{cell}.json")
        spec["configs"].append({"name": config,
                                "file": f"bench/configs/{config}.json"})
        spec["workloads"].append({"name": cell, "config": config,
                                  "traffic": traffic, "chips": 1})
    for m in real["per_layer"]:
        m = dict(m)
        if "workloads" in m:
            m["workloads"] = [c for c, v in CELLS.items()
                              if v[2] in m["workloads"]]
        spec["per_layer"].append(m)
    return tmp, spec


def run(root: Path, spec, cell: str, *, seed: int = 7, trace: bool = False,
        seconds: float = 0.2):
    """One CPU run of ``cell`` through the harness, past its look for a
    chip."""
    from bench import harness

    return harness.run_cell(spec, root, cell, seed, seconds, trace,
                            time.perf_counter(), allow_cpu=True,
                            log=lambda msg: None)
