"""CLI for the federated systems simulation on the paper's logreg task.

Runs one algorithm under one aggregation policy over simulated wall-clock
time and reports per-round and summary systems metrics (simulated time,
stragglers dropped, bytes moved) alongside the algorithmic ones (objective,
accuracy). The algorithm math is exactly core/'s -- the sim only decides
WHO participates (from simulated arrival times) and WHAT the server holds
(dequantized uploads when the codec is on).

The CLI is a thin shim over the declarative experiment spec layer
(``repro.spec``, docs/spec.md): legacy flags are mapped onto an
``ExperimentSpec`` and built through the same ``spec.build()`` path a
``--spec`` file takes, with bit-for-bit identical trajectories either way.

Usage:
  python -m repro.launch.simulate --spec examples/specs/fig7_async.toml
  python -m repro.launch.simulate --spec examples/specs/golden_sync.toml \
      --engine scan --rounds 50              # spec file + overrides
  python -m repro.launch.simulate --alg fedepm --aggregation deadline \
      --deadline 0.002 --latency pareto --m 50 --rounds 30 --d 4000
  python -m repro.launch.simulate --alg fedepm --aggregation sync \
      --topk 0.25 --bits 8 --error-feedback   # compressed, EF memory
  python -m repro.launch.simulate --alg fedepm --aggregation async \
      --buffer-size 8 --latency pareto        # FedBuff-style buffered
  python -m repro.launch.simulate --alg sfedavg --aggregation async \
      --max-concurrency 6 --buffer-size 4 \
      --trace-file tests/fixtures/device_trace.csv   # client-level dispatch
  python -m repro.launch.simulate --alg sfedavg --aggregation overselect \
      --overselect 1.5 --latency lognormal
  python -m repro.launch.simulate --alg fedepm --aggregation deadline \
      --deadline 0.002 --fault-drop 0.1 --fault-transient 0.2 \
      --fault-corrupt 0.05                    # lossy uplink (docs/sim.md)

Aggregation modes: sync (wait for all), deadline (drop stragglers past
--deadline, eq. (22) carry-through), adaptive (per-client EWMA-learned
deadlines), overselect (contact a uniform candidate set at rate
rho*--overselect, keep the first ceil(rho*m) arrivals), async (client-
level dispatch: per-client start/upload events with an optional
--max-concurrency in-flight cap, aggregate every --buffer-size arrivals
with staleness-weighted merges; one reported "round" = one aggregation
event; all three algorithms run under identical async semantics).
``--policy`` is accepted as an alias of ``--aggregation``. A knob that
belongs to a different policy than the one selected is an ERROR, not
silently ignored (the spec layer enforces the same ownership rules).
Device fleets come from --trace-file (resampled real logs) or the
synthetic lognormal profiles. Full semantics: docs/sim.md.

``--engine scan`` runs EVERY policy through the fused on-device round
engine (repro.sim.engine). Clocked policies compile K rounds into one
``lax.scan`` with donated state buffers and the participation-mask stream
precomputed; the async policy records its event loop per chunk and
replays it as one compiled scan over a fixed-capacity payload table. Both
reproduce the eager trajectory bit-for-bit -- states, metrics, byte
ledger and telemetry event stream -- at a fraction of the host dispatch
overhead, and ``--terminate`` stops at exactly the eager stopping round
(docs/perf.md, benchmarks/bench_engine.py):

  python -m repro.launch.simulate --alg fedepm --aggregation sync \
      --engine scan --m 50 --rounds 200
  python -m repro.launch.simulate --alg fedepm --aggregation async \
      --buffer-size 4 --engine scan --rounds 200
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.launch.compile_cache import enable_compile_cache
from repro.spec import (
    AlgorithmSpec,
    CodecSpec,
    EngineSpec,
    ExperimentSpec,
    FaultSpec,
    FleetSpec,
    PolicySpec,
    PrivacySpec,
    SpecError,
    TaskSpec,
)
from repro.spec.build import SIM_KNOB_DEFAULTS
from repro.spec.registry import ASYNC_KNOBS

# argparse defaults for the policy-scoped knobs -- the SINGLE source both
# for ap.add_argument(default=...) and for the unset test in
# spec_from_args (a value AT its default is treated as "unset", so the
# ownership validation only fires for knobs the user actually supplied;
# the async knobs use None sentinels instead -- passing their literal
# default to the wrong policy must still error). The values themselves
# come from SimConfig's dataclass defaults (repro.spec.build), except
# --deadline whose CLI surface keeps the historical "<= 0 means
# infinite" encoding of SimConfig's inf default.
_KNOB_DEFAULTS = {
    "deadline": 0.0,
    "overselect": SIM_KNOB_DEFAULTS["overselect_factor"],
    "deadline_slack": SIM_KNOB_DEFAULTS["deadline_slack"],
    "ewma_beta": SIM_KNOB_DEFAULTS["ewma_beta"],
}


def spec_from_args(args) -> ExperimentSpec:
    """Map the legacy flag surface onto an ExperimentSpec.

    The mapping is exact: building the returned spec reproduces the
    trajectory the historical ``build_sim`` flag plumbing produced,
    bit-for-bit (tests/test_spec.py).
    """
    policy_kw = {}
    if args.deadline > 0:                          # <= 0 means infinite
        policy_kw["deadline"] = args.deadline      # misplaced -> SpecError
    if args.aggregation == "overselect" \
            or args.overselect != _KNOB_DEFAULTS["overselect"]:
        policy_kw["overselect_factor"] = args.overselect
    if args.aggregation == "adaptive":
        policy_kw["deadline_slack"] = args.deadline_slack
        policy_kw["ewma_beta"] = args.ewma_beta
    else:
        for knob in ("deadline_slack", "ewma_beta"):
            if getattr(args, knob) != _KNOB_DEFAULTS[knob]:
                policy_kw[knob] = getattr(args, knob)
    for knob in sorted(ASYNC_KNOBS):               # None = not passed
        if getattr(args, knob) is not None:
            policy_kw[knob] = getattr(args, knob)

    if args.trace_file:
        fleet = FleetSpec(kind="trace", trace_file=args.trace_file,
                          latency=args.latency,
                          latency_sigma=args.latency_sigma,
                          latency_alpha=args.latency_alpha)
    else:
        fleet = FleetSpec(
            kind="synthetic",
            availability=args.availability if args.availability != 1.0
            else None,
            latency=args.latency, latency_sigma=args.latency_sigma,
            latency_alpha=args.latency_alpha)

    # getattr default: hand-built Namespaces (tests, library callers)
    # predate the fault flags and simply get the fault-free defaults
    fault_kw = {spec_field: getattr(args, flag, None)
                for flag, spec_field in _FAULT_FLAGS.items()
                if getattr(args, flag, None) is not None}

    privacy_kw = {}
    if getattr(args, "dp_eps", None) is not None:
        privacy_kw["eps"] = args.dp_eps
    if getattr(args, "dp_clip", None) is not None:
        # an explicit clip bound selects the enforced-clip sensitivity
        # mode (the surrogate mode never clips)
        privacy_kw["sensitivity"] = "clip"
        privacy_kw["clip"] = args.dp_clip
    if getattr(args, "secure_agg", False):
        privacy_kw["secure_agg"] = True
    if getattr(args, "privacy_seed", None) is not None:
        privacy_kw["seed"] = args.privacy_seed

    return ExperimentSpec(
        name=f"cli/{args.alg}-{args.aggregation}",
        seed=args.seed,
        task=TaskSpec(kind="logreg", d=args.d, n=args.n, m=args.m),
        algorithm=AlgorithmSpec(name=args.alg, rho=args.rho, k0=args.k0,
                                eps_dp=args.eps),
        fleet=fleet,
        policy=PolicySpec(name=args.aggregation, **policy_kw),
        codec=CodecSpec(topk_frac=args.topk, bits=args.bits,
                        impl=args.quant_impl,
                        error_feedback=args.error_feedback),
        faults=FaultSpec(**fault_kw),
        privacy=PrivacySpec(**privacy_kw),
        engine=EngineSpec(name=args.engine, rounds=args.rounds,
                          terminate=args.terminate))


# CLI fault flags (args attribute -> FaultSpec field). None sentinels: an
# unset flag leaves the FaultSpec default (all rates zero -> no fault
# model, the exact pre-fault simulation).
_FAULT_FLAGS = {
    "fault_drop": "drop_rate",
    "fault_transient": "transient_rate",
    "fault_corrupt": "corrupt_rate",
    "fault_duplicate": "duplicate_rate",
    "fault_max_retries": "max_retries",
    "fault_seed": "seed",
}


def _telemetry_overrides(args) -> dict:
    """--telemetry/--events-out/--trace-out/--jax-profile -> dotted spec
    overrides. Any sink flag implies telemetry.enabled (a sink without a
    recorder would be a guaranteed validation error)."""
    overrides = {}
    if args.events_out:
        overrides["telemetry.events_jsonl"] = args.events_out
    if args.trace_out:
        overrides["telemetry.trace_out"] = args.trace_out
    if args.jax_profile:
        overrides["telemetry.jax_profiler_dir"] = args.jax_profile
    if args.telemetry or overrides:
        overrides["telemetry.enabled"] = True
    return overrides


def resolve_spec(args) -> ExperimentSpec:
    """--spec file (plus explicit overrides) or the legacy-flag mapping."""
    if not args.spec:
        exp = spec_from_args(args)
        overrides = _telemetry_overrides(args)
        return (exp.replace(**overrides) if overrides else exp).validate()
    exp = ExperimentSpec.load(args.spec)
    overrides = _telemetry_overrides(args)
    if args.engine_flag is not None:
        overrides["engine.name"] = args.engine_flag
    if args.rounds_flag is not None:
        overrides["engine.rounds"] = args.rounds_flag
    if args.terminate_flag:
        overrides["engine.terminate"] = True
    if args.seed_flag is not None:
        overrides["seed"] = args.seed_flag
    return (exp.replace(**overrides) if overrides else exp).validate()


def run(args) -> dict:
    exp = resolve_spec(args)
    handle = exp.build()
    m = exp.task.m

    def report(met, f):
        if args.quiet:
            return
        head = (f"round {met.round_idx:3d}  f/m={f / m:.6f}  " if f is not None
                else f"round {met.round_idx:3d}  ")
        print(head
              + f"t={met.t_total:9.4f}s (+{met.t_round:.4f})  "
                f"agg={met.n_aggregated}/{met.n_contacted} "
                f"drop={met.n_dropped}  "
                f"up={met.bytes_up/1e3:.1f}kB "
                f"down={met.bytes_down/1e3:.1f}kB"
              + ("  ABANDONED" if met.abandoned else ""), flush=True)

    summary = handle.run(report=report)
    if not args.quiet:
        print("\nsummary:")
        for k, v in summary.items():
            print(f"  {k:28s} {v}")
    return summary


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Federated systems simulation (stragglers, deadlines, "
                    "byte ledger) on the paper logreg task")
    ap.add_argument("--spec", default=None,
                    help="ExperimentSpec file (.toml/.json, docs/spec.md); "
                         "replaces the legacy flags below -- only "
                         "--engine/--rounds/--terminate/--seed and the "
                         "telemetry flags override the file, plus "
                         "--quiet/--json")
    ap.add_argument("--alg", default="fedepm",
                    choices=["fedepm", "sfedavg", "sfedprox"])
    ap.add_argument("--aggregation", "--policy", dest="aggregation",
                    default="sync",
                    choices=["sync", "deadline", "adaptive", "overselect",
                             "async"],
                    help="aggregation mode (--policy is an alias)")
    ap.add_argument("--engine", dest="engine_flag", default=None,
                    choices=["eager", "scan"],
                    help="round execution engine: 'eager' dispatches one "
                         "jit call per round (the semantic reference); "
                         "'scan' compiles multi-round chunks into one "
                         "on-device lax.scan with donated state buffers -- "
                         "bit-identical trajectory, far fewer host syncs "
                         "(docs/perf.md). async aggregation record/replays "
                         "its event loop through the same compiled path; "
                         "--terminate stops at exactly the eager stopping "
                         "round. Default: eager, or the spec file's engine")
    ap.add_argument("--deadline", type=float,
                    default=_KNOB_DEFAULTS["deadline"],
                    help="deadline policy cutoff in simulated seconds "
                         "(<= 0 means infinite)")
    ap.add_argument("--buffer-size", type=int, default=None,
                    help="async: contributions per aggregation event "
                         "(0 = cohort size, which recovers sync exactly)")
    ap.add_argument("--staleness-exp", type=float, default=None,
                    help="async: stale merges weighted (1+s)^-exp "
                         "(default 0.5)")
    ap.add_argument("--max-concurrency", type=int, default=None,
                    help="async: cap on in-flight clients; dispatches past "
                         "the cap queue until an upload frees a slot "
                         "(0 = unlimited, which dispatches whole cohorts)")
    ap.add_argument("--deadline-slack", type=float,
                    default=_KNOB_DEFAULTS["deadline_slack"],
                    help="adaptive: per-client wait budget = slack * EWMA")
    ap.add_argument("--ewma-beta", type=float,
                    default=_KNOB_DEFAULTS["ewma_beta"],
                    help="adaptive: EWMA weight of the newest latency")
    ap.add_argument("--overselect", type=float,
                    default=_KNOB_DEFAULTS["overselect"],
                    help="over-selection factor: contact a uniform "
                         "candidate set at rate rho*f, keep the first "
                         "ceil(rho*m) arrivals")
    ap.add_argument("--latency", default="deterministic",
                    choices=["deterministic", "lognormal", "pareto"])
    ap.add_argument("--latency-sigma", type=float, default=0.5)
    ap.add_argument("--latency-alpha", type=float, default=1.2)
    ap.add_argument("--availability", type=float, default=1.0,
                    help="P(client reachable per round) for the synthetic "
                         "profiles; a --trace-file fleet carries its own "
                         "availability column instead")
    ap.add_argument("--trace-file", default=None,
                    help="CSV/JSON device trace; the fleet is resampled "
                         "from it instead of the synthetic lognormal "
                         "profiles (schema: sim/clients.py::LatencyTrace; "
                         "overrides --availability)")
    ap.add_argument("--m", type=int, default=50)
    ap.add_argument("--n", type=int, default=14)
    ap.add_argument("--d", type=int, default=4000,
                    help="dataset size (4000 = reduced task; paper: 45222)")
    ap.add_argument("--rounds", dest="rounds_flag", type=int, default=None,
                    help="round budget (default 30, or the spec file's)")
    ap.add_argument("--rho", type=float, default=0.5)
    ap.add_argument("--k0", type=int, default=8)
    ap.add_argument("--eps", type=float, default=0.0,
                    help="DP epsilon (0 disables noise)")
    ap.add_argument("--topk", type=float, default=1.0,
                    help="codec: fraction of coordinates uploaded")
    ap.add_argument("--bits", type=int, default=0,
                    help="codec: quantization bits (0 = raw values)")
    ap.add_argument("--error-feedback", action="store_true",
                    help="codec: EF21-style memory (compress residuals "
                         "against the shared reconstruction)")
    ap.add_argument("--quant-impl", default="ref",
                    choices=["ref", "pallas"])
    ap.add_argument("--fault-drop", type=float, default=None,
                    help="fault injection: P(an upload attempt is lost "
                         "mid-flight) -- billed but never arrives "
                         "(docs/sim.md fault model)")
    ap.add_argument("--fault-transient", type=float, default=None,
                    help="fault injection: P(an upload attempt fails "
                         "transiently); the server retries with "
                         "exponential backoff, each attempt billed")
    ap.add_argument("--fault-corrupt", type=float, default=None,
                    help="fault injection: P(an upload arrives corrupted); "
                         "the server screens and rejects it, repeat "
                         "offenders are quarantined")
    ap.add_argument("--fault-duplicate", type=float, default=None,
                    help="fault injection: P(a successful upload is "
                         "delivered twice); the server dedups by sequence "
                         "number, the copy is billed and discarded")
    ap.add_argument("--fault-max-retries", type=int, default=None,
                    help="fault injection: retry budget per contribution "
                         "before the client is abandoned for the round "
                         "(default 2)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="fault injection: dedicated RNG seed (default: "
                         "derived from --seed; fault draws never perturb "
                         "the latency stream)")
    ap.add_argument("--dp-eps", type=float, default=None,
                    help="upload privacy: per-round per-client DP epsilon "
                         "budget; uploads are Laplace-noised on the wire "
                         "and the accountant tracks spent budget "
                         "(docs/privacy.md). Distinct from the "
                         "in-algorithm --eps noise")
    ap.add_argument("--dp-clip", type=float, default=None,
                    help="upload privacy: enforce ||z||_1 <= clip before "
                         "noising and use the data-independent 2*clip "
                         "sensitivity (default: the paper's 2*||z||_1 "
                         "surrogate; requires --dp-eps)")
    ap.add_argument("--secure-agg", action="store_true",
                    help="upload privacy: bill one pairwise-mask exchange "
                         "per upload attempt that reaches the wire "
                         "(32 bytes each; composes with --fault-* retries)")
    ap.add_argument("--privacy-seed", type=int, default=None,
                    help="upload privacy: dedicated noise-stream seed "
                         "(default: derived from --seed; noise draws never "
                         "perturb the latency or codec streams; requires "
                         "--dp-eps or --secure-agg)")
    ap.add_argument("--seed", dest="seed_flag", type=int, default=None,
                    help="master seed (default 0, or the spec file's)")
    ap.add_argument("--terminate", dest="terminate_flag",
                    action="store_true",
                    help="stop at the paper's termination rule")
    ap.add_argument("--telemetry", action="store_true",
                    help="attach the run-telemetry recorder (events + "
                         "metrics; docs/observability.md). The trajectory "
                         "is bit-for-bit unchanged; the summary gains a "
                         "'telemetry' block. Implied by any sink flag "
                         "below. Composes with --spec")
    ap.add_argument("--events-out", default=None,
                    help="telemetry sink: write the event stream as JSONL "
                         "(one event per line; implies --telemetry)")
    ap.add_argument("--trace-out", default=None,
                    help="telemetry sink: write a Perfetto/Chrome "
                         "trace_event JSON of the simulated timeline -- "
                         "one track per client -- loadable in "
                         "ui.perfetto.dev (implies --telemetry)")
    ap.add_argument("--jax-profile", default=None, metavar="DIR",
                    help="wrap the run in jax.profiler for a real "
                         "wall-time trace under DIR (implies --telemetry)")
    ap.add_argument("--quiet", action="store_true")
    ap.add_argument("--json", default=None,
                    help="write the summary dict to this path")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # legacy-surface defaults (the spec file's values win under --spec)
    args.engine = args.engine_flag or "eager"
    args.rounds = args.rounds_flag if args.rounds_flag is not None else 30
    args.seed = args.seed_flag if args.seed_flag is not None else 0
    args.terminate = args.terminate_flag

    if args.rounds < 1:
        ap.error("--rounds must be >= 1")
    if args.buffer_size is not None and args.buffer_size < 0:
        ap.error("--buffer-size must be >= 0 (0 = cohort size)")
    if args.max_concurrency is not None and args.max_concurrency < 0:
        ap.error("--max-concurrency must be >= 0 (0 = unlimited)")
    if args.staleness_exp is not None and args.staleness_exp < 0:
        ap.error("--staleness-exp must be >= 0")
    if args.spec:
        # the spec file IS the experiment; a legacy flag alongside it
        # would be silently ignored, which the spec layer forbids --
        # detectably-supplied ones (off-default) are hard errors
        ignored = [f"--{k.replace('_', '-')}"
                   for k in ("alg", "aggregation", "deadline", "overselect",
                             "deadline_slack", "ewma_beta", "latency",
                             "latency_sigma", "latency_alpha",
                             "availability", "trace_file", "m", "n", "d",
                             "rho", "k0", "eps", "topk", "bits",
                             "error_feedback", "quant_impl",
                             *sorted(_FAULT_FLAGS),
                             "dp_eps", "dp_clip", "secure_agg",
                             "privacy_seed",
                             *sorted(ASYNC_KNOBS))
                   if getattr(args, k) != ap.get_default(k)]
        if ignored:
            ap.error(f"{', '.join(ignored)} cannot be combined with "
                     f"--spec (the file defines the experiment; only "
                     f"--engine/--rounds/--terminate/--seed override it)")
    elif args.aggregation != "async":
        passed = [f"--{k.replace('_', '-')}" for k in sorted(ASYNC_KNOBS)
                  if getattr(args, k) is not None]
        if passed:
            ap.error(f"{', '.join(passed)} only valid with "
                     f"--aggregation async; got --aggregation "
                     f"{args.aggregation}")
    if args.error_feedback and args.topk >= 1.0 and args.bits == 0:
        ap.error("--error-feedback needs a lossy codec: set --topk < 1 "
                 "and/or --bits > 0")
    # privacy knob ownership, mirroring the spec layer: a knob supplied
    # without the state it configures is an error, never silently unused
    if args.dp_clip is not None and not (args.dp_eps and args.dp_eps > 0):
        ap.error("--dp-clip bounds the DP noise sensitivity; it requires "
                 "--dp-eps > 0")
    if args.privacy_seed is not None and not (
            (args.dp_eps and args.dp_eps > 0) or args.secure_agg):
        ap.error("--privacy-seed keys the privacy noise stream; it "
                 "requires --dp-eps > 0 or --secure-agg")
    if args.trace_file and args.availability != 1.0:
        ap.error("--availability conflicts with --trace-file: the trace's "
                 "own availability column defines the fleet")

    try:
        summary = run(args)
    except SpecError as e:
        ap.error(str(e))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
