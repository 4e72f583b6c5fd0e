"""Shared test setup: put ``src`` and the repo root on sys.path."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir, "src"))
# repo root, so tests can import the benchmarks/ modules they exercise
sys.path.insert(1, os.path.join(os.path.dirname(__file__), os.pardir))


def pytest_report_header(config):
    """Say up front whether the property tests run on real hypothesis or
    the seeded-loop fallback (tests/_propshim.py) — so a CI log always
    records which engine produced the run."""
    try:
        import hypothesis
        return f"property tests: hypothesis {hypothesis.__version__}"
    except ImportError:
        return ("property tests: hypothesis NOT installed — seeded-loop "
                "fallback (tests/_propshim.py; no shrinking)")
