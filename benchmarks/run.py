"""Benchmark harness: one module per paper table/figure. Prints CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # CPU-budget settings
    REPRO_BENCH_FULL=1 python -m benchmarks.run        # paper-scale settings
    PYTHONPATH=src python -m benchmarks.run --only fig4_comm,fig11_batchsize
    PYTHONPATH=src python -m benchmarks.run --list     # registry + one-liners
"""
from __future__ import annotations

import argparse
import csv
import io
import sys
import time

from . import (fig3_accuracy, fig4_comm, fig5_ablations, fig6_kvasir,
               fig11_batchsize, fig_async, fig_blocks, fig_compress,
               fig_dropout, fig_hier, fig_kernels, fig_ragged, mia_privacy,
               roofline, table2_histo)

# name -> (module, paper anchor, runtime tier). The one-line description
# shown by ``--list`` is each module's own docstring first line, so
# registry and docs cannot drift apart; tests assert every fig_* file on
# disk is here. The TIER selects figures for ``--tier``: "fast" figures
# finish in CPU minutes at default settings; "full" figures are accuracy
# sweeps that only make sense at paper scale.
MODULES = {
    "fig3_accuracy": (fig3_accuracy, "Fig. 3 / Fig. 9", "full"),
    "fig4_comm": (fig4_comm, "Fig. 4 / Fig. 13", "full"),
    "fig5_ablations": (fig5_ablations, "Fig. 5 a-c / Fig. 12", "full"),
    "fig6_kvasir": (fig6_kvasir, "Fig. 6", "full"),
    "table2_histo": (table2_histo, "Fig. 8 / Table 2", "full"),
    "fig11_batchsize": (fig11_batchsize, "Fig. 11", "full"),
    "fig_ragged": (fig_ragged, "beyond-paper", "full"),
    "fig_blocks": (fig_blocks, "beyond-paper", "fast"),
    "fig_kernels": (fig_kernels, "beyond-paper", "fast"),
    "fig_hier": (fig_hier, "beyond-paper", "fast"),
    "fig_compress": (fig_compress, "beyond-paper", "full"),
    "fig_async": (fig_async, "beyond-paper", "full"),
    "fig_dropout": (fig_dropout, "paper §3.4", "full"),
    "mia_privacy": (mia_privacy, "beyond-paper", "full"),
    "roofline": (roofline, "§Roofline", "full"),
}

TIERS = ("fast", "full")


def names_for_tier(tier: str) -> list:
    """Registry names whose runtime tier is ``tier``, as ``--tier``
    selects them."""
    if tier not in TIERS:
        raise ValueError(f"unknown tier {tier!r}; expected one of {TIERS}")
    return [n for n, (_, _, t) in MODULES.items() if t == tier]


def _describe(name: str) -> str:
    mod, anchor, tier = MODULES[name]
    first = (mod.__doc__ or "").strip().splitlines()
    return (f"{name}: [{anchor}] ({tier}) "
            f"{first[0] if first else '(no docstring)'}")


def list_benchmarks() -> list:
    """Registry listing, one line per benchmark (also the --list output)."""
    return [_describe(name) for name in MODULES]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--only", default="",
                    help="comma-separated subset of benchmark names")
    ap.add_argument("--list", action="store_true",
                    help="print every registered benchmark with its "
                         "one-line description and runtime tier, and exit")
    ap.add_argument("--tier", choices=TIERS, default="",
                    help="run only benchmarks of this runtime tier")
    ap.add_argument("--full", action="store_true", help="paper-scale settings")
    args = ap.parse_args(argv)
    if args.list:
        for line in list_benchmarks():
            print(line)
        return 0
    names = [n.strip() for n in args.only.split(",") if n.strip()] or list(MODULES)
    if args.tier:
        allowed = set(names_for_tier(args.tier))
        names = [n for n in names if n in allowed]

    failures = 0
    for name in names:
        mod = MODULES[name][0]
        t0 = time.time()
        print(f"\n===== {name} =====", flush=True)
        try:
            rows = mod.run(args.full) if args.full else mod.run()
        except Exception as e:
            print(f"BENCH FAILED {name}: {type(e).__name__}: {e}",
                  file=sys.stderr)
            failures += 1
            continue
        if not rows:
            print("(no rows)")
            continue
        keys = sorted({k for r in rows for k in r})
        buf = io.StringIO()
        w = csv.DictWriter(buf, fieldnames=keys)
        w.writeheader()
        for r in rows:
            w.writerow(r)
        print(buf.getvalue().rstrip())
        print(f"[{name}: {len(rows)} rows in {time.time()-t0:.1f}s]")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
