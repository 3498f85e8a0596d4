"""Public API facade for the FedAdp reproduction.

The curated, stable import surface — everything a training script needs
without reaching into `repro.core.*`:

    import repro

    cfg = repro.FLConfig(num_clients=10, clients_per_round=10,
                         local_steps=12, aggregation="buffered",
                         buffer_m=7).validate()
    server = repro.FedServer("mlr", cfg, nodes, test, batch_size=50)
    hist = server.run(300, target_acc=0.85, mode="scanned")

`__all__` is pinned by tests/test_api.py; grow it deliberately. The
deeper modules (`repro.core`, `repro.kernels`, `repro.transport`, ...)
remain importable for tests and internals, but scripts/examples/
benchmarks go through this facade.
"""
from repro.core.clients import ClientModel, lm_client  # noqa: F401
from repro.core.fl import (  # noqa: F401
    FLConfig,
    RoundState,
    init_round_state,
    make_round_fn,
    state_from_tree,
    state_to_tree,
)
from repro.core.server import (  # noqa: F401
    FedServer,
    History,
    fixed_arrival_schedule,
)
from repro import telemetry  # noqa: F401
from repro.launch.mesh import make_mesh, use_compile_cache  # noqa: F401
from repro.telemetry.manifest import run_manifest  # noqa: F401
from repro.telemetry.sinks import (  # noqa: F401
    CSVSink,
    JSONLSink,
    MemorySink,
)
from repro.telemetry.spans import SpanTimer  # noqa: F401

__all__ = [
    "CSVSink",
    "ClientModel",
    "FLConfig",
    "FedServer",
    "History",
    "JSONLSink",
    "MemorySink",
    "RoundState",
    "SpanTimer",
    "fixed_arrival_schedule",
    "init_round_state",
    "lm_client",
    "make_mesh",
    "make_round_fn",
    "run_manifest",
    "state_from_tree",
    "state_to_tree",
    "telemetry",
    "use_compile_cache",
]
