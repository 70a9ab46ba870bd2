"""The bench entry points' platform rule: measure on what was asked for.

A bench that cannot get its platform fails; it never moves to another one.
The platform asked for is the first entry of ``JAX_PLATFORMS``; with the
variable unset the ask is "the accelerator", so a backend that came up as
``cpu`` is a failure too.  An explicit ``JAX_PLATFORMS=cpu`` is an
intentional, labelled CPU run (CI's ``perf-smoke`` uses it).
"""

from __future__ import annotations


def require_device() -> str:
    """The platform this process measures on; SystemExit when it is not
    the one asked for.  This is the process's first backend touch."""
    from arrow_ballista_tpu.utils import asked_platform, resolve_backend

    try:
        platform = resolve_backend()["platform"]
    except RuntimeError as e:
        raise SystemExit(f"bench: cannot get the platform asked for: {e}")
    if platform == "cpu" and not asked_platform():
        raise SystemExit(
            "bench: no accelerator came up and JAX_PLATFORMS is unset; set "
            "JAX_PLATFORMS=cpu for an intentional CPU run"
        )
    return platform
