"""Run one benchmark cell on the machine this starts on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The last line of standard output is the
run's JSON result; the numbers the correctness check compared, each beside
its limit, are the last lines of standard error. Exits non-zero, printing no
result, when JAX finds no accelerator or fewer chips than the cell needs, or
when the program is not beside the benchmark.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness

    return harness.main(sys.argv[1:], ROOT, T0)


if __name__ == "__main__":
    sys.exit(main())
