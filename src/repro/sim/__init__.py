"""Federated systems runtime: straggler simulation, sync/deadline/adaptive/
overselect/async-buffered aggregation, upload codec with optional error
feedback, seeded fault injection (drops, retries, duplicates, corruption,
quarantine), and a byte-accurate communication ledger around the core
round functions. Architecture notes live in docs/sim.md; the declarative
experiment layer that drives this runtime from TOML/JSON specs is
repro.spec (docs/spec.md)."""
from repro.sim.clients import (          # noqa: F401
    AdaptiveDeadlines,
    ClientProfiles,
    LatencyTrace,
    latency_model_names,
    make_latency_model,
    make_profiles,
    register_latency_model,
    round_arrivals,
    uniform_profiles,
)
from repro.sim.faults import (           # noqa: F401
    FaultConfig,
    FaultModel,
    build_fault_model,
)
from repro.sim.server import (           # noqa: F401
    FedSim,
    SimConfig,
    SimMetrics,
    client_work_flops,
)
from repro.sim.engine import (           # noqa: F401
    EngineResult,
    lower_rounds,
    run_rounds,
    run_to_objective,
)
from repro.sim.transport import (        # noqa: F401
    ByteLedger,
    CodecConfig,
    codec_roundtrip,
    ef_roundtrip,
    encoded_client_bytes,
    stacked_client_bytes,
    tree_client_bytes,
)
