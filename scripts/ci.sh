#!/usr/bin/env bash
# Single CI entry point: tier-1 test suite + headless quickstart example.
#
#   scripts/ci.sh             # full tier-1 run (ROADMAP verify command)
#   scripts/ci.sh --lint      # static analysis, reproduces the CI lint job:
#                             # fedlint (tools/fedlint — the five engine
#                             # correctness contracts from docs/INVARIANTS.md:
#                             # rng-discipline, trace-hygiene, carry-coverage,
#                             # fingerprint-coverage, kernel-dtype) over
#                             # src/ + benchmarks/, then the curated ruff
#                             # baseline (ruff.toml) over the whole tree.
#                             # ruff is skipped with a banner when not
#                             # installed (minimal containers); fedlint is
#                             # stdlib-only and always runs. FEDLINT_FORMAT=
#                             # github switches to workflow annotations.
#   scripts/ci.sh --fast      # only tests marked @pytest.mark.fast; includes
#                             # the fast slice of the cross-backend
#                             # conformance matrix (tests/test_conformance.py:
#                             # loop==vmap, ragged-on-vmap, blocked==per-round
#                             # bitwise, the async-τ0==vmap equivalence smoke,
#                             # async-τ2 block/resume bit-identity, the
#                             # Pallas fused-vs-plain hot-path parity, and
#                             # the compressed-exchange parity slice:
#                             # compress=none bitwise-identical to the
#                             # uncompressed protocol on every backend,
#                             # plus topk/int8 loop-vs-vmap columns with
#                             # the privacy epsilon compared EXACTLY —
#                             # compression must never touch the
#                             # accountant) plus
#                             # the interpret-mode kernel smoke slice
#                             # (tests/test_kernels.py: fused PushSum mix,
#                             # stale mix, noise→SGD/Adam step vs the ref
#                             # oracles) so every PR exercises every compiled
#                             # path including the fused kernels
#   scripts/ci.sh --smoke     # resume-correctness smoke: 4-client federation
#                             # killed after round 2 of 3 and resumed (per-
#                             # round, rounds_per_block=2 kill-after-block,
#                             # the async-τ2 stale-buffer scenario AND the
#                             # hier-τ2 cross-shard-buffer scenario) must
#                             # be bit-identical to uninterrupted runs
#   scripts/ci.sh --shard I/N # deterministic 1-based slice of the test FILES
#                             # (sorted, round-robin) — the GitHub workflow
#                             # matrixes the full suite across shards; the
#                             # quickstart example runs on shard 1 only and
#                             # the heterogeneous-archs example on shard 2
#                             # (shards 1 and 2 always exist: CI's smallest
#                             # matrix is 3-way), so every example executes
#                             # exactly once per matrixed run
#
# The full suite exceeds 10 minutes serial, so pytest runs with `-n auto`
# whenever pytest-xdist is importable and falls back to serial when it is
# not (minimal containers stay supported).
#
# Extra arguments after the mode flag are forwarded to pytest.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

# plain strings (not arrays): empty arrays break under `set -u` on bash < 4.4
MARK=""
SHARD=""
if [[ "${1:-}" == "--lint" ]]; then
  shift
  echo "== lint: fedlint (engine correctness contracts) =="
  python -m tools.fedlint src benchmarks --format="${FEDLINT_FORMAT:-text}"
  if python -c "import ruff" >/dev/null 2>&1 || command -v ruff >/dev/null 2>&1; then
    echo "== lint: ruff (curated baseline, ruff.toml) =="
    if [[ "${FEDLINT_FORMAT:-}" == "github" ]]; then
      ruff check --output-format=github .
    else
      ruff check .
    fi
  else
    echo "== lint: ruff NOT installed — SKIPPED (CI runs it; install ruff"
    echo "   locally to reproduce the full lint job) =="
  fi
  echo "CI OK"
  exit 0
elif [[ "${1:-}" == "--fast" ]]; then
  MARK="-m fast"
  shift
elif [[ "${1:-}" == "--smoke" ]]; then
  shift
  echo "== smoke: checkpoint/resume bit-identity (round-blocks + async-τ2 + hier-τ2) + commitment verify-after-resume / refuse-after-bitflip =="
  python scripts/resume_smoke.py
  echo "CI OK"
  exit 0
elif [[ "${1:-}" == "--shard" ]]; then
  SHARD="${2:?--shard needs I/N (e.g. 1/2)}"
  shift 2
fi

XDIST=""
if python -c "import xdist" >/dev/null 2>&1; then
  XDIST="-n auto"
fi

# Property tests (hypothesis) skip cleanly when the library is absent
# (tests/_hypothesis_compat); -rs below makes pytest print the counted
# skip-reason summary so the logs record exactly what did not run.
if python -c "import hypothesis" >/dev/null 2>&1; then
  echo "== property tests: hypothesis available =="
else
  echo "== property tests: hypothesis NOT installed — property-based tests"
  echo "   will be SKIPPED (pinned deterministic twins still run; see the"
  echo "   'property test skipped' count in the pytest skip summary) =="
fi

if [[ -n "$SHARD" ]]; then
  I="${SHARD%%/*}"
  N="${SHARD##*/}"
  FILES=""
  i=0
  for f in tests/test_*.py; do  # glob order is sorted and stable
    if (( i % N == I - 1 )); then FILES="$FILES $f"; fi
    i=$((i + 1))
  done
  if [[ -z "$FILES" ]]; then
    # an empty slice (I > N or I > file count) must fail loudly — bare
    # pytest would silently collect the WHOLE tree instead
    echo "error: shard $SHARD selects no test files" >&2
    exit 1
  fi
  echo "== tier-1 shard $SHARD: pytest$FILES =="
  # shellcheck disable=SC2086  # FILES/XDIST intentionally word-split
  python -m pytest -x -q -rs $XDIST $FILES "$@"
  if [[ "$I" == "1" ]]; then
    echo "== example: quickstart (headless) =="
    python examples/quickstart.py
  elif [[ "$I" == "2" ]]; then
    # quickstart runs on shard 1; without this branch no shard ever
    # executed the heterogeneous-archs example and a regression there
    # would only surface in local full runs
    echo "== example: heterogeneous archs (headless) =="
    python examples/heterogeneous_archs.py
  fi
  echo "CI OK"
  exit 0
fi

echo "== tier-1: pytest =="
# shellcheck disable=SC2086  # MARK/XDIST intentionally word-split
python -m pytest -x -q -rs $MARK $XDIST "$@"

echo "== example: quickstart (headless) =="
python examples/quickstart.py

echo "CI OK"
