"""First-class observability: jit-pure drift telemetry, round-trace spans
(on the profiler's clock when enabled), the round program's named scopes,
pluggable sinks, and the BENCH_*.json perf trajectory.

Attach a trace to any experiment (both runtimes):

    from repro.obs import JsonlSink, attach
    exp = build_experiment("fedpac_soap", scenario="cifar_like_cnn")
    attach(exp, JsonlSink("runs/trace.jsonl"))
    exp.run()

The trace then carries one ``round`` event per server update (metrics +
on-device ``Telemetry``: drift norm, beta trajectory, staleness histogram,
per-client geometry distances, update/correction alignment, wire bytes)
plus ``span`` events for each phase and explicit ``client_dropped`` events
from the async scheduler.  ``FedExperiment.log_round`` routes through the
same ``Sink`` protocol (``exp.sink``), defaulting to the legacy-bitwise
stdout formatting.
"""
from repro.obs.bench import (  # noqa: F401
    BENCH_SCHEMA_VERSION, make_bench, read_bench, validate_bench,
    write_bench,
)
from repro.obs.sinks import (  # noqa: F401
    CsvSink, JsonlSink, MemorySink, Sink, StdoutRoundSink, format_metric,
)
from repro.obs.telemetry import (  # noqa: F401
    STALENESS_BINS, Telemetry, client_geom_dist, collect,
    staleness_histogram, telemetry_dict,
)
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER, PHASES, SCOPES, Tracer, validate_event, validate_jsonl,
)

__all__ = [
    "BENCH_SCHEMA_VERSION", "CsvSink", "JsonlSink", "MemorySink",
    "NULL_TRACER", "PHASES", "SCOPES", "STALENESS_BINS", "Sink",
    "StdoutRoundSink", "Telemetry", "Tracer", "attach", "client_geom_dist",
    "collect", "format_metric", "make_bench", "read_bench",
    "staleness_histogram", "telemetry_dict", "validate_bench",
    "validate_event", "validate_jsonl", "write_bench",
]


def attach(exp, *sinks, run_id=None) -> Tracer:
    """Wire trace sinks into an experiment; returns the live ``Tracer``.

    ``exp`` is any ``FedExperiment``; subsequent rounds emit span/round/
    drop events into every sink.  Passing no sinks detaches (restores the
    disabled tracer).  An experiment whose local optimizer has a curvature
    refresh first records how each of its matrices is computed (one
    ``refresh_routes`` event)."""
    tracer = Tracer(sinks=sinks, run_id=run_id)
    exp.tracer = tracer
    if sinks and exp.opt is not None and exp.opt.refresh_routes is not None:
        tracer.emit("refresh_routes", optimizer=exp.opt.name,
                    routes=exp.opt.refresh_routes(exp.server.params))
    return tracer
