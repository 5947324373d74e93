"""Opt-in ``jax.profiler`` hook for real wall-time traces.

Simulated-time telemetry (events.py / trace.py) describes what the modeled
fleet did; this module answers the other question -- where the WALL time of
the scan engine actually goes (compile vs. dispatch vs. device compute).
``jax_profile(trace_dir)`` wraps a run in ``jax.profiler.start_trace`` /
``stop_trace``; the resulting TensorBoard/Perfetto trace lands under
``trace_dir``. A falsy ``trace_dir`` makes it a no-op. When a trace was
requested, a failed profiler start or stop raises: a run that was asked
for a trace and silently wrote none would pass for a traced one.

The program names its own work in that trace, always (no switch: with
the profiler off a ``TraceAnnotation`` costs about a microsecond and
a ``jax.named_scope`` only names the ops it wraps):

* ``DEVICE_SCOPES`` -- ``jax.named_scope`` names of the round's device
  stages. They land in each compiled op's ``op_name`` metadata (a fusion
  takes its root's), wrapped by JAX's transform names (``jvp(...)``,
  ``transpose(...)``), and both engines share the code that opens them.
* ``HOST_SPANS`` -- ``jax.profiler.TraceAnnotation`` names of the host
  phases, each opened with the round index it starts at (``round=``).

These two tuples are the one list of names: tests check every scope and
span the source opens against them.
"""
from __future__ import annotations

import contextlib

DEVICE_SCOPES = (
    "ens",             # core/fedepm.py: ENS aggregation (19)
    "ens_select",      # kernels/ens/ref.py: ENS's median by min/max
                       # (m <= 8), inside "ens"; the rest of "ens" sorts
    "client_grad",     # core/fedepm.py: the vmapped client gradient (18)
    "attention",       # models/dense.py: flash attention, fwd and bwd
    "attention_window",  # models/dense.py: a windowed layer's attention
                         # in a stack of mixed layer kinds
    "moe_route",       # models/moe.py: held experts' router, top-k and
                       # gates
    "moe_experts",     # models/moe.py: the held experts' gated matmuls
    "client_prox",     # core/fedepm.py: k0 closed-form prox steps (20)
    "dp_noise",        # core/fedepm.py, core/baselines.py: DP-noised upload
    "upload_codec",    # sim/transport.py: codec round trip
    "upload_ef",       # sim/transport.py: error-feedback round trip
    "upload_privacy",  # sim/transport.py: clip + noise round trips
    "merge",           # sim/engine.py: the async replay's merge body
    "client_update",   # core/baselines.py: SFedAvg/SFedProx local steps
    "aggregate",       # core/baselines.py: mean of the selected uploads
)

HOST_SPANS = (
    "repro.run.objective",        # spec/build.py: f(w) reads, one a
                                  # round (eager), one a chunk (scan)
    "repro.run.final_objective",  # spec/build.py: summary's f(w) read
    "repro.engine.arrivals",      # sim/engine.py: a chunk's arrival draws
    "repro.engine.candidates",    # one candidate-stream pass + readback
    "repro.engine.policy",        # host float64 policy replay
    "repro.engine.noise",         # host-side unit-noise draws
    "repro.engine.dispatch",      # the chunk call (+ w_tau readback)
    "repro.engine.bookkeeping",   # per-round host loop, one per chunk
    "repro.engine.async_record",  # the async recording pass
    "repro.engine.async_replay",  # the async replay dispatch
    "repro.sim.step",             # sim/server.py: one eager FedSim.step
)


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
