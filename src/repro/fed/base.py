"""Shared federated-experiment interface.

``FedExperiment`` is the runtime-agnostic contract that both the lock-step
synchronous runtime (``fed.rounds.FederatedExperiment``) and the buffered
asynchronous runtime (``fed.async_runtime.AsyncFederatedExperiment``)
implement, so benchmarks and examples can swap execution models without
touching algorithm code.  One ``run_round()`` is one server model update —
a communication round in the sync runtime, a buffer flush in the async one.

The base class owns the config/rounds contract: subclasses call
``super().__init__(fed)`` with any config exposing an integer ``rounds``
attribute (``FedConfig`` in-tree), which also initializes ``history``.
Round logging goes through the single overridable ``log_round`` hook,
which routes through the observability sink protocol (``repro.obs``):
``self.sink`` receives one ``round`` event per logged round, defaulting to
``StdoutRoundSink`` — byte-identical to the legacy print formatting.
``self.tracer`` is the round-trace span recorder (disabled until sinks are
attached via ``repro.obs.attach``).

``make_experiment`` picks the runtime from ``FedConfig.runtime`` — it is
the legacy positional constructor; prefer ``repro.api.build_experiment``.
"""
from __future__ import annotations

import abc
from typing import Optional

from repro.obs.sinks import StdoutRoundSink
from repro.obs.sinks import format_metric as _format_metric
from repro.obs.trace import Tracer


class FedExperiment(abc.ABC):
    """Drives server model updates for any algorithm over client datasets.

    Contract declared here (not ad hoc in subclasses):
      fed      — the experiment config; must expose an int ``rounds``
      history  — list of per-round metric dicts, appended by run_round()
      scenario — the materialized ``repro.scenarios.Scenario`` bundle when
                 the experiment was built from a declarative scenario
                 (``build_experiment(..., scenario=...)``); None otherwise
      sink     — ``repro.obs.Sink`` receiving ``log_round`` round events
                 (default: legacy-bitwise stdout formatting)
      tracer   — ``repro.obs.Tracer`` for span/round/drop trace events;
                 disabled (no sinks) unless ``repro.obs.attach``-ed
      last_telemetry — the most recent jit-pure ``Telemetry`` pytree
                 (None before the first round)
      opt      — the clients' ``repro.optim.LocalOptimizer`` (set by each
                 runtime; None on a bare subclass)
      server   — the server state, with the global model in ``params``
    """

    fed: "FedConfig"     # noqa: F821 — any config with an int .rounds
    history: list
    scenario = None      # set by repro.api.build_experiment
    opt = None           # set by each runtime

    def __init__(self, fed):
        rounds = getattr(fed, "rounds", None)
        if not isinstance(rounds, int) or isinstance(rounds, bool):
            raise TypeError(
                "FedExperiment config must expose an integer 'rounds' "
                f"attribute (got {type(fed).__name__} with "
                f"rounds={rounds!r}) — pass a FedConfig or a compatible "
                "config object")
        self.fed = fed
        self.history = []
        self.sink = StdoutRoundSink()
        self.tracer = Tracer()       # disabled until obs.attach()
        self.last_telemetry = None

    @abc.abstractmethod
    def run_round(self) -> dict:
        """Advance the server by one model update; returns the metrics row."""

    @abc.abstractmethod
    def comm_bytes_per_round(self) -> int:
        """Per-client upload bytes for one round (Table 6 accounting)."""

    # 4-decimal rounding for floats; everything else (ints, None, strings,
    # arrays from custom eval fns) passes through untouched.
    format_metric = staticmethod(_format_metric)

    def log_round(self, rec: dict, r: int) -> None:
        """Per-round logging hook; routes through ``self.sink`` (override
        either this hook or the sink to redirect metrics).  The emitted
        event mirrors the tracer's ``round`` events minus the trace-stream
        sequencing (logging and tracing are independent channels)."""
        self.sink.emit({"event": "round", "run_id": self.tracer.run_id,
                        "round": r, "metrics": rec})

    def run(self, rounds: Optional[int] = None, log_every: int = 0):
        """Run ``rounds`` model updates (default: ``self.fed.rounds``)."""
        for r in range(rounds if rounds is not None else self.fed.rounds):
            rec = self.run_round()
            if log_every and (r % log_every == 0):
                self.log_round(rec, r)
        return self.history


def make_experiment(fed, params, loss_fn, client_batch_fn, eval_fn=None,
                    opt_kwargs=None, async_cfg=None) -> FedExperiment:
    """Instantiate the runtime named by ``fed.runtime`` ("sync" | "async").

    Legacy positional entry point; ``repro.api.build_experiment`` is the
    keyword builder that also accepts ``AlgorithmSpec`` values directly.
    """
    if fed.runtime == "sync":
        if async_cfg is not None:
            raise ValueError(
                "async_cfg given but fed.runtime='sync' — set "
                "FedConfig(runtime='async') or drop the async_cfg")
        from repro.fed.rounds import FederatedExperiment
        return FederatedExperiment(fed, params, loss_fn, client_batch_fn,
                                   eval_fn, opt_kwargs)
    if fed.runtime == "async":
        from repro.fed.async_runtime import AsyncFederatedExperiment
        return AsyncFederatedExperiment(fed, params, loss_fn, client_batch_fn,
                                        eval_fn, opt_kwargs,
                                        async_cfg=async_cfg)
    raise ValueError(f"unknown runtime {fed.runtime!r} (want 'sync'|'async')")
