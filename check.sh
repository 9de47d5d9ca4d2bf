#!/bin/sh
# Lint + tier-1 test gate with a wall-clock budget.
# Usage: ./check.sh            (full gate)
#        CHECK_BUDGET_S=600 ./check.sh
# Fails fast on lint regressions and on slow-test creep (the pytest
# run is killed — and the gate fails — past the budget).  The pytest
# flags are the driver's (/root/TESTS_LAST_RUN.json): six xdist
# workers, one file per worker at a time.
set -u
cd "$(dirname "$0")"

BUDGET="${CHECK_BUDGET_S:-1470}"

if command -v ruff >/dev/null 2>&1; then
    echo "== ruff =="
    ruff check pilosa_tpu tests benchmark || exit 1
else
    echo "check.sh: ruff not installed — skipping lint" >&2
fi

echo "== tier-1 (budget ${BUDGET}s) =="
# per-run log (concurrent gates must not clobber each other);
# no pipe around pytest: under plain sh a `... | tee` pipeline would
# report tee's exit status and the gate could never fail
T1LOG="$(mktemp /tmp/_t1.XXXXXX.log)"
trap 'rm -f "$T1LOG"' EXIT
timeout -k 10 "$BUDGET" env JAX_PLATFORMS=cpu \
    python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider \
    -p xdist -n 6 --dist loadfile -p no:randomly > "$T1LOG" 2>&1
rc=$?
cat "$T1LOG"
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' "$T1LOG" | tr -cd . | wc -c)"
if [ "$rc" -eq 124 ] || [ "$rc" -eq 137 ]; then
    echo "check.sh: tier-1 exceeded the ${BUDGET}s budget" >&2
fi
exit "$rc"
