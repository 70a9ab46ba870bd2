"""Per-thread XLA compile accounting, from jax's own monitoring events.

The stage compiler's ``kernel_compiles`` only sees the jitted kernels it
wraps; eager ops, the gang's ``shard_map`` step and the packed fetch
compile too.  jax reports every executable it obtains — compiled fresh or
loaded from the persistent cache — through ``jax.monitoring``, and calls
the listeners on the thread that asked for it, so a thread-local counter
attributes them to the task that paid for them without any locking.

``xla_compiles`` counts executables obtained (zero on a repeat of the
same query in a warm process); ``xla_compile_ns`` is the time spent
getting them; ``xla_cache_hits`` says how many came from the persistent
cache instead of the compiler (a fresh process next to a warm cache
directory shows hits and a small ``xla_compile_ns``).
"""

from __future__ import annotations

import threading

import jax.monitoring

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_FIELDS = ("xla_compiles", "xla_compile_ns", "xla_cache_hits")

_tls = threading.local()


def _counts() -> dict:
    c = getattr(_tls, "counts", None)
    if c is None:
        c = _tls.counts = dict.fromkeys(_FIELDS, 0)
    return c


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    if event == _BACKEND_COMPILE:
        c = _counts()
        c["xla_compiles"] += 1
        c["xla_compile_ns"] += int(duration_secs * 1e9)


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT:
        _counts()["xla_cache_hits"] += 1


jax.monitoring.register_event_duration_secs_listener(_on_duration)
jax.monitoring.register_event_listener(_on_event)


def snapshot() -> dict:
    """This thread's running totals (pass to :func:`since`)."""
    return dict(_counts())


def since(before: dict) -> dict:
    """Non-zero growth of this thread's totals since ``before``."""
    now = _counts()
    return {
        k: now[k] - before[k] for k in _FIELDS if now[k] != before[k]
    }


def credit(counts: dict) -> None:
    """Add ``counts`` (a :func:`since` taken on another thread) to this
    thread's totals: what a worker obtained for this thread's task."""
    c = _counts()
    for k, v in counts.items():
        c[k] += v
