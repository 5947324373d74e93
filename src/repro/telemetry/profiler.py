"""Opt-in ``jax.profiler`` hook for real wall-time traces.

Simulated-time telemetry (events.py / trace.py) describes what the modeled
fleet did; this module answers the other question -- where the WALL time of
the scan engine actually goes (compile vs. dispatch vs. device compute).
``jax_profile(trace_dir)`` wraps a run in ``jax.profiler.start_trace`` /
``stop_trace``; the resulting TensorBoard/Perfetto trace lands under
``trace_dir``. A falsy ``trace_dir`` makes it a no-op. When a trace was
requested, a failed profiler start or stop raises: a run that was asked
for a trace and silently wrote none would pass for a traced one.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def jax_profile(trace_dir):
    """Context manager tracing wall time via jax.profiler; no-op if falsy."""
    if not trace_dir:
        yield
        return
    import jax
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()
