#!/usr/bin/env bash
# Fast regression gate: tier-1 tests, the clock-discipline lint
# (scheduling code reads time through the Clock abstraction only, which
# the deterministic traces depend on), and repo hygiene checks: no
# .pyc/__pycache__ artifact is ever tracked, and no generated result
# file under benchmarks/results/ is ever tracked (stale by
# construction).
#
#     scripts/smoke.sh            # tier-1 tests + lint + hygiene
#     scripts/smoke.sh --quick    # lint + hygiene only
#     SMOKE_SKIP_TESTS=1 scripts/smoke.sh   # same as --quick
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

for arg in "$@"; do
  case "$arg" in
    --quick) SMOKE_SKIP_TESTS=1 ;;
    *) echo "unknown argument: $arg" >&2; exit 2 ;;
  esac
done

if [[ -z "${SMOKE_SKIP_TESTS:-}" ]]; then
  python -m pytest -x -q
fi

python scripts/lint_clock.py

# repo hygiene: compiled artifacts must never be tracked
if git ls-files | grep -E '(^|/)__pycache__/|\.pyc$' >/dev/null; then
  echo "smoke: FAIL — tracked .pyc/__pycache__ artifacts:" >&2
  git ls-files | grep -E '(^|/)__pycache__/|\.pyc$' >&2
  exit 1
fi
# results are regenerated every run; a tracked copy under
# benchmarks/results/ would go stale the moment it lands and silently
# shadow fresh numbers in any tooling that reads the checkout instead
# of running the benchmark — fail fast if one ever gets committed
if git ls-files -- benchmarks/results | grep . >/dev/null; then
  echo "smoke: FAIL — tracked result artifacts (stale by" \
       "construction):" >&2
  git ls-files -- benchmarks/results >&2
  exit 1
fi
echo "smoke: OK"
