"""Shared helpers of the benchmark's CPU tests.

Run from the repository root: ``JAX_PLATFORMS=cpu python -m pytest
bench/tests``.  The cells run at a tiny row count on the CPU through
``run_cell``'s overrides; the chip check is skipped there.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_ROWS = 20_000  # 118 pages: every build quantum in its 64-page bucket
FAKE_PEAKS = {"hbm_bytes_per_s": 1e10}  # no CPU figure is a device peak


def tiny_config(rows: int = TINY_ROWS) -> dict:
    """The configuration cut for a CPU test: fewer rows, and tuning
    cycles as often per statement as at 10M rows."""
    return {
        "n_rows": rows,
        "tuner": {
            "kind": "PredictiveTuner",
            "storage_budget_bytes": 1e9,
            "candidate_min_count": 2,
            "max_build_pages_per_cycle": 64,
            "tuning_interval_ms": 10_000.0 * rows / 10_000_000,
        },
    }


TINY_TRAFFIC = {
    "tuner_narrow.shift_read_heavy": {
        "loop": {"kind": "open", "arrivals": "poisson", "rate_per_s": 40.0,
                 "burst_max": 8},
    },
    "tuner_wide.analytic_scan": {},
}


@pytest.fixture(scope="session")
def run_tiny():
    """run_tiny(cell, seed, seconds, trace, **kw) -> result object."""
    from bench.run import run_cell

    def go(cell, seed, seconds=2.0, trace=False, **kw):
        kw.setdefault("config_override", tiny_config())
        kw.setdefault("traffic_override", TINY_TRAFFIC[cell])
        return run_cell(cell, seed, seconds, trace, require_tpu=False,
                        peaks_override=FAKE_PEAKS, **kw)

    return go
