#!/usr/bin/env bash
# Canonical pre-merge check: lint gate + the fast tier-1 slice on CPU
# with the Pallas kernels in interpret mode (repro.kernels.ops picks
# interpret mode exactly when JAX's backend is the CPU; on a TPU the
# kernels compile).
#
#   scripts/ci.sh            lint (if ruff installed) + fast slice
#   scripts/ci.sh --full     lint + everything, incl. @pytest.mark.slow
#   scripts/ci.sh --lint     lint only (fails hard if ruff is missing;
#                            the CI workflow's dedicated lint job)
#   scripts/ci.sh <args...>  extra args forwarded to pytest
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Formatter adoption is incremental (see pyproject.toml): new modules
# are kept `ruff format`-clean; legacy hand-aligned modules join this
# list as they get reformatted.
RUFF_FORMAT_PATHS=(
    src/repro/api.py
    src/repro/bench_db/runner.py
    src/repro/core/build_service.py
    src/repro/core/cost_model.py
    src/repro/core/engine.py
    src/repro/core/executor.py
    src/repro/core/forecaster.py
    src/repro/core/hybrid_scan.py
    src/repro/core/monitor.py
    src/repro/core/planner.py
    src/repro/core/replica.py
    src/repro/core/tuner.py
    src/repro/faults
    src/repro/kernels
    src/repro/parallel
    src/repro/serving
)

# Tracked-artifact gate: bytecode, pytest caches, the JAX compile
# cache and benchmark JSON must never be committed (.gitignore covers
# them; this catches force-adds and stale history).  Exception:
# benchmarks/baselines/ holds the COMMITTED trajectory seed the
# nightly gate falls back to before its cache has a point (see
# .github/workflows/ci.yml).
tracked_artifacts() {
    git ls-files | grep -E '(^|/)__pycache__/|\.pyc$|(^|/)\.pytest_cache/|(^|/)\.jax_cache/|(^|/)BENCH_[^/]*\.json$|(^|/)bench-[^/]*\.json$' \
        | grep -v '^benchmarks/baselines/BENCH_[^/]*\.json$' || true
}

artifact_gate() {
    local bad
    bad="$(tracked_artifacts)"
    if [[ -n "$bad" ]]; then
        echo "ci.sh: tracked build artifacts found (purge with git rm --cached):" >&2
        echo "$bad" >&2
        exit 1
    fi
}

lint() {
    artifact_gate
    ruff check .
    ruff format --check "${RUFF_FORMAT_PATHS[@]}"
}

if [[ "${1:-}" == "--lint" ]]; then
    lint
    exit 0
fi

if command -v ruff >/dev/null 2>&1; then
    lint
else
    artifact_gate   # the tracked-artifact gate needs no ruff
    echo "ci.sh: ruff not installed; skipping lint gate" \
         "(pip install -r requirements-dev.txt)" >&2
fi

if [[ "${1:-}" == "--full" ]]; then
    shift
    exec python -m pytest -q -m "slow or not slow" "$@"
fi
exec python -m pytest -q "$@"
