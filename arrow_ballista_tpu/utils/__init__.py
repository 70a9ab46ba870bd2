"""Shared utilities."""

from __future__ import annotations

import os


def asked_platform(env=os.environ) -> str:
    """The platform ``JAX_PLATFORMS`` names first ("" when unset)."""
    return env.get("JAX_PLATFORMS", "").split(",")[0].strip()


def resolve_backend() -> dict:
    """Touch the jax backend NOW, on purpose, and say what came up.

    A chip belongs to one process: the process that runs kernels claims it
    at start-up through this call, so a busy or absent chip is a start-up
    error with jax's own message instead of a first query that hangs.
    jax raises by itself when a platform listed in ``JAX_PLATFORMS`` cannot
    initialise; this adds the check that the platform named FIRST there is
    the one that became the default.

    Returns ``{"platform", "device_kind", "device_count"}`` as jax reports
    them in this process.
    """
    import jax

    asked = asked_platform()
    devices = jax.devices()
    platform = devices[0].platform
    if asked and asked != platform:
        raise RuntimeError(
            f"JAX_PLATFORMS asks for {asked!r} but the default backend "
            f"is {platform!r}"
        )
    return {
        "platform": platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }
