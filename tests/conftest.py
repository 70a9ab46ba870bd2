"""Test configuration: force an 8-device virtual CPU mesh so multi-chip
sharding paths are exercised without TPU hardware (SURVEY.md §4 rebuild
implication: single-host multi-chip tests replace docker-compose)."""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# Tests always run on the CPU platform, whatever JAX_PLATFORMS says on the
# machine (a chip host sets it to the accelerator).
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def tpch_ctx():
    """Session context with all 8 TPC-H tables at SF 0.01, 2 partitions."""
    from arrow_ballista_tpu import SessionContext
    from benchmarks.tpch.datagen import register_all

    ctx = SessionContext()
    register_all(ctx, sf=0.01, partitions=2)
    return ctx
