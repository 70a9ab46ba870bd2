"""Tests of the benchmark's own code.  Run: python -m pytest benchmark/tests -q
(CPU only; nothing here touches a chip or reports a device number)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="session")
def small_data(tmp_path_factory):
    """lineitem/orders/customer at SF0.01, 12 files a table, seed 5."""
    from benchmark import suites

    out = str(tmp_path_factory.mktemp("tpch"))
    info = suites.generate(out, suites.load("tpch"), ("lineitem", "orders", "customer"), 0.01, 5, 12, procs=2)
    return out, info
