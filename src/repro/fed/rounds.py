"""Synchronous federated runtime: client sampling, batch staging, round loop.

Algorithms are first-class ``AlgorithmSpec`` values resolved from the
registry (``core.algorithms``) — the legacy strings from the paper's tables
all resolve there:

  fedavg                         SGD locally, parameter averaging
  scaffold                       control variates (core/scaffold.py)
  fedcm                          client momentum == correction-only + SGD
  local_{adamw,sophia,muon,soap} FedSOA (Alg. 1) with that optimizer
  fedpac_{sophia,muon,soap}      FedPAC (Alg. 2)
  fedpm_{sophia,muon,soap}       preconditioned mixing (core/fedpm.py)
  + component ablations (align_only / correct_only) and _light (SVD upload)

The runtime is a thin driver over the unified round engine
(``core.engine``): it samples cohorts and stages batches; the round itself
is the spec-built uniform driver (``core.algorithms.build_round_fn``) —
one signature for every algorithm, per-client persistent state (SCAFFOLD's
control variates) included.  The buffered-asynchronous execution model of
the same specs lives in ``fed.async_runtime``; both implement
``fed.base.FedExperiment``.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro import optim
from repro.core import init_server
from repro.core.algorithms import (
    AlgorithmSpec, build_round_fn, init_round_client_state, resolve,
)
from repro.core.engine import BETA_MAX_AUTO, ExecutorConfig, make_controller
from repro.core.transport import (
    Transport, validate_codec_spec, validate_wire_dtype,
)
from repro.fed.base import FedExperiment
from repro.obs.telemetry import telemetry_dict
from repro.utils import hw
from repro.fed.staging import (
    StagingBuffers, produce_cohort_batches, stack_cohort_batches,
)

RUNTIMES = ("sync", "async")


@dataclasses.dataclass
class FedConfig:
    algorithm: str = "fedpac_soap"
    n_clients: int = 20
    participation: float = 0.2     # fraction sampled per round
    rounds: int = 20
    local_steps: int = 10          # K
    batch_size: int = 16
    lr: Optional[float] = None     # default: paper's per-optimizer lr
    beta: Union[float, str] = 0.5  # FedPAC correction strength (or "auto")
    hessian_freq: int = 10
    svd_rank: int = 8              # low-rank codec rank (*_light variants)
    seed: int = 0
    server_lr: float = 1.0
    runtime: str = "sync"          # "sync" | "async" (fed.base.make_experiment)
    executor: str = "vmap"         # cohort executor:
    #                                vmap|shard_map|chunked|sharded
    chunk_size: int = 8            # for executor="chunked"/"sharded"
    # ---- population scale-out (fed.population). None -> legacy dense path
    # (n_clients dense lists, shared-RNG draw order preserved bitwise).
    population_size: Optional[int] = None  # abstract client-id space size
    cohort_size: Optional[int] = None      # clients per round (required
    #                                        when population_size is set)
    state_budget: Optional[int] = None     # resident client-state slots;
    #                                        None -> min(pop, 4 * cohort)
    cohort_sampler: str = "uniform"        # population cohort sampler name
    spill_dir: Optional[str] = None        # cold-state spill dir (None ->
    #                                        a fresh temp dir)
    # geometry transport (core.transport): None inherits the spec's declared
    # codec specs (upload / delta_upload); strings may chain with "+"
    theta_codec: Optional[str] = None
    delta_codec: Optional[str] = None
    error_feedback: bool = True    # EF residuals for lossy delta codecs
    qblock_size: int = 128         # qblock codec: elements per scale
    sketch_iters: int = 2          # power_sketch subspace iterations
    use_pallas: Optional[bool] = None  # Pallas wire kernels; None -> auto
                                       # (real kernels on TPU, off elsewhere)
    wire_dtype: str = "f32"        # wire payload dtype: "f32" (native,
                                   # lossless) | "bf16" (half-width uploads)
    # ---- chunk-streaming pipelined rounds (fed.pipeline): overlap host
    # staging + state I/O with device compute.  Population + sync only.
    pipeline: bool = False
    pipeline_chunk: int = 128      # clients per pipeline chunk
    pipeline_workers: int = 4      # background stager threads

    def __post_init__(self):
        if not (0.0 < self.participation <= 1.0):
            raise ValueError(
                f"participation must be in (0, 1], got {self.participation}")
        if self.runtime not in RUNTIMES:
            raise ValueError(
                f"unknown runtime {self.runtime!r} (want one of {RUNTIMES})")
        self.executor_config()   # ExecutorConfig validates backend/chunk_size
        if self.n_clients < 1:
            raise ValueError(f"n_clients must be >= 1, got {self.n_clients}")
        if self.local_steps < 1:
            raise ValueError(
                f"local_steps must be >= 1, got {self.local_steps}")
        if self.hessian_freq < 1:
            raise ValueError(
                f"hessian_freq must be >= 1, got {self.hessian_freq}")
        if isinstance(self.beta, str) and self.beta != "auto":
            raise ValueError(
                f"beta must be a float or 'auto', got {self.beta!r}")
        for codec_spec in (self.theta_codec, self.delta_codec):
            if codec_spec is not None:
                validate_codec_spec(codec_spec)  # UnknownCodecError early
        if self.svd_rank < 1:
            raise ValueError(f"svd_rank must be >= 1, got {self.svd_rank}")
        if self.qblock_size < 1:
            raise ValueError(
                f"qblock_size must be >= 1, got {self.qblock_size}")
        if hw.resolve_use_pallas(self.use_pallas) and self.qblock_size % 128:
            raise ValueError(
                f"qblock_size must be a multiple of 128 (VPU lane width) "
                f"when Pallas kernels are enabled, got {self.qblock_size}")
        validate_wire_dtype(self.wire_dtype)
        if self.sketch_iters < 0:
            raise ValueError(
                f"sketch_iters must be >= 0, got {self.sketch_iters}")
        if self.pipeline_chunk < 1:
            raise ValueError(
                f"pipeline_chunk must be >= 1, got {self.pipeline_chunk}")
        if self.pipeline_workers < 1:
            raise ValueError(
                f"pipeline_workers must be >= 1, got "
                f"{self.pipeline_workers}")
        self._validate_population()
        if self.pipeline:
            if not self.population_active:
                raise ValueError(
                    "pipeline=True requires population mode (the chunked "
                    "cohort stream and sparse state store) — set "
                    "population_size/cohort_size as well")
            if self.runtime != "sync":
                raise ValueError(
                    "pipeline=True is a sync-runtime feature (the async "
                    "runtime already overlaps dispatches); use "
                    "runtime='sync'")

    def _validate_population(self):
        if self.population_size is None:
            pop_only = {"cohort_size": self.cohort_size,
                        "state_budget": self.state_budget,
                        "spill_dir": self.spill_dir}
            stray = [k for k, v in pop_only.items() if v is not None]
            if self.cohort_sampler != "uniform":
                stray.append("cohort_sampler")
            if stray:
                raise ValueError(
                    f"{', '.join(sorted(stray))} only apply to population "
                    "mode — set population_size as well")
            return
        if self.population_size < 1:
            raise ValueError(
                f"population_size must be >= 1, got {self.population_size}")
        if self.cohort_size is None:
            raise ValueError(
                "population mode needs an explicit cohort_size "
                "(participation fractions don't scale to 10^6-id spaces)")
        if not 1 <= self.cohort_size <= self.population_size:
            raise ValueError(
                f"cohort_size must be in [1, population_size="
                f"{self.population_size}], got {self.cohort_size}")
        if self.state_budget is not None and \
                self.state_budget < self.cohort_size:
            raise ValueError(
                f"state_budget {self.state_budget} < cohort_size "
                f"{self.cohort_size}: every cohort member needs a resident "
                "state slot")
        from repro.fed.population.directory import SAMPLERS
        if self.cohort_sampler not in SAMPLERS:
            raise ValueError(
                f"unknown cohort_sampler {self.cohort_sampler!r} (config "
                f"strings support {sorted(SAMPLERS)}; pass a "
                "ClientPopulation for weighted/availability sampling)")

    @property
    def population_active(self) -> bool:
        return self.population_size is not None

    def resolve_state_budget(self) -> int:
        """Resident client-state slots: explicit budget, else enough for a
        few cohorts of churn without population-proportional memory."""
        if self.state_budget is not None:
            return self.state_budget
        return min(self.population_size, 4 * self.cohort_size)

    def executor_config(self) -> ExecutorConfig:
        return ExecutorConfig(backend=self.executor,
                              chunk_size=self.chunk_size)

    def make_transport(self, spec: AlgorithmSpec) -> Transport:
        """Resolve the wire policy for ``spec`` under this config."""
        return spec.make_transport(
            rank=self.svd_rank, block=self.qblock_size,
            sketch_iters=self.sketch_iters,
            delta_codec=self.delta_codec, theta_codec=self.theta_codec,
            error_feedback=self.error_feedback, use_pallas=self.use_pallas,
            wire_dtype=self.wire_dtype)


def parse_algorithm(name: str):
    """Legacy flag-tuple view of an algorithm string.

    -> (optimizer_name, align, correct, light).  Deprecated: strings now
    resolve to registered ``AlgorithmSpec`` values (``core.algorithms``);
    this shim survives for callers that still want the PR-2-era tuple.
    Prefer ``repro.core.algorithms.resolve(name)`` — the spec additionally
    carries the beta policy, upload codec, client-state protocol, and
    mixing hook that this tuple cannot express.
    """
    spec = resolve(name)
    return spec.optimizer, spec.align, spec.correct, spec.upload == "svd"


def resolve_lr(fed: FedConfig, spec_or_opt: Union[AlgorithmSpec, str]
               ) -> float:
    """Explicit fed.lr wins — including falsy values like 0.0 — then the
    spec's declared default_lr, then the optimizer's paper-table default."""
    if fed.lr is not None:
        return fed.lr
    if isinstance(spec_or_opt, AlgorithmSpec):
        if spec_or_opt.default_lr is not None:
            return spec_or_opt.default_lr
        spec_or_opt = spec_or_opt.optimizer
    return optim.DEFAULT_LR.get(spec_or_opt, 1e-2)


class FederatedExperiment(FedExperiment):
    """Drives R lock-step communication rounds over client datasets.

    ``client_batch_fn(client_id, rng) -> batch pytree`` supplies one local
    minibatch; batches for a round are stacked to (S, K, ...).

    ``spec`` (optional) supplies the algorithm directly — an unregistered
    ``AlgorithmSpec`` works; ``fed.algorithm`` is only consulted when it is
    None.  The spec is resolved once here and reused for the round fn, the
    optimizer, and comm accounting.

    Population mode (``fed.population_size`` set, optionally with an
    explicit ``population=`` carrying a weighted/availability sampler):
    cohorts stream from the abstract id space, every per-client draw
    derives from ``fold_in(seed, client_id)`` (round salt separates
    rounds), per-client state lives in a budgeted sparse store
    (``fed.population.make_client_store``) whose cold rows spill through
    the checkpoint store, and the round_fn receives *slot* indices plus
    pre-derived stacked keys.  The legacy path (``population_size=None``)
    keeps its shared-generator draw order bitwise-intact.
    """

    def __init__(self, fed: FedConfig, params, loss_fn: Callable,
                 client_batch_fn: Callable, eval_fn: Optional[Callable] = None,
                 opt_kwargs: Optional[dict] = None,
                 spec: Optional[AlgorithmSpec] = None,
                 population: Optional[object] = None):
        super().__init__(fed)
        self.spec = resolve(spec if spec is not None else fed.algorithm)
        self.loss_fn = loss_fn
        self.client_batch_fn = client_batch_fn
        self.eval_fn = eval_fn
        self.rng = np.random.default_rng(fed.seed)

        self.population = self._resolve_population(population)
        n_for_state = (fed.population_size if self.population is not None
                       else fed.n_clients)
        self.opt = self.spec.make_optimizer(**(opt_kwargs or {}))
        self.lr = resolve_lr(fed, self.spec)
        beta = self.spec.resolve_beta(fed.beta)
        self.transport = fed.make_transport(self.spec)
        self.round_fn = build_round_fn(
            self.spec, loss_fn, self.opt, lr=self.lr,
            local_steps=fed.local_steps, beta=beta,
            hessian_freq=fed.hessian_freq, server_lr=fed.server_lr,
            transport=self.transport,
            executor=fed.executor_config(), n_clients=n_for_state,
            telemetry=True)
        geom = make_controller(beta, correct=self.spec.correct,
                               beta_max=BETA_MAX_AUTO)
        self.server = init_server(params, self.opt, geom=geom)
        if self.population is not None:
            from repro.core.algorithms import round_client_state_spec
            from repro.fed.population import make_client_store
            self.state_store = make_client_store(
                round_client_state_spec(self.spec, self.transport), params,
                fed.population_size, budget=fed.resolve_state_budget(),
                spill_dir=fed.spill_dir)
            self.client_state = (self.state_store.state
                                 if self.state_store is not None else None)
        else:
            self.state_store = None
            self.client_state = init_round_client_state(
                self.spec, self.transport, params, fed.n_clients)
        # persistent host staging buffers: host-side batch fns refill the
        # same (S, K, ...) arrays every round instead of re-allocating
        self._staging_buffers = StagingBuffers()
        self.pipeline = None
        if fed.pipeline:
            if self.spec.mixing is not None:
                import warnings
                warnings.warn(
                    f"algorithm {self.spec.name!r} has a mixing hook, "
                    "which needs the decoded cohort stack; pipeline=True "
                    "falls back to the serial round", RuntimeWarning,
                    stacklevel=2)
            else:
                from repro.fed.pipeline import RoundPipeline
                self.pipeline = RoundPipeline(self)

    def _resolve_population(self, population):
        from repro.fed.population import resolve_population
        return resolve_population(self.fed, population)

    # ------------------------------------------------------------ staging

    def _sample_cohort(self):
        s = max(1, int(round(self.fed.n_clients * self.fed.participation)))
        return self.rng.choice(self.fed.n_clients, size=s, replace=False)

    def _stage_batches(self, cohort):
        """Stack per-client, per-step batches -> leading (S, K, ...) axes;
        the per-client batch production is the ``stage_batches`` span."""
        with self.tracer.span("stage_batches", round=self.server.round + 1):
            per_client = produce_cohort_batches(
                self.client_batch_fn, cohort, self.fed.local_steps, self.rng)
        return stack_cohort_batches(per_client, self._staging_buffers)

    def _stage_population(self, round_index: int):
        """One population round's inputs: streamed cohort, fold_in-derived
        batches and stacked keys (round_index as the salt), and the cohort's
        state-store *slots* (acquire materializes/restores rows).  The
        host-phase split ("stage_batches" vs "state_acquire" spans) is what
        the executor benchmarks read back to attribute serial round time."""
        from repro.fed.population import stage_population_batches
        t = self.tracer
        pop = self.population
        cohort = pop.sample_cohort(round_index, self.fed.cohort_size)
        with t.span("stage_batches", round=round_index + 1):
            batches = stage_population_batches(
                self.client_batch_fn, pop, cohort, self.fed.local_steps,
                salt=round_index)
        keys = pop.cohort_keys(cohort, salt=round_index)
        with t.span("state_acquire", round=round_index + 1):
            slots = (self.state_store.acquire(cohort)
                     if self.state_store is not None else cohort)
        return slots, batches, keys

    # ------------------------------------------------------------ loop

    def run_round(self):
        t = self.tracer
        rnum = self.server.round + 1   # the round this update produces
        if self.pipeline is not None:
            # chunk-streaming pipelined round: staging/restores/compute
            # interleave per chunk (fed.pipeline emits its own spans) and
            # the driver advances server/client_state itself
            metrics = self.pipeline.run_round()
        else:
            with t.span("staging", round=rnum):
                if self.population is not None:
                    slots, batches, key = self._stage_population(rnum - 1)
                else:
                    cohort = self._sample_cohort()
                    batches = self._stage_batches(cohort)
                    key = jax.random.key(int(self.rng.integers(0, 2**31)))
                    slots = cohort
            # one jitted call fuses local update + wire encode +
            # aggregation; the span blocks on the result only when someone
            # is tracing
            with t.span("update", round=rnum):
                cstate = (self.state_store.state
                          if self.state_store is not None
                          else self.client_state)
                self.server, self.client_state, metrics = self.round_fn(
                    self.server, cstate, jnp.asarray(slots), batches, key)
                if self.state_store is not None:
                    self.state_store.state = self.client_state
                if t.enabled:
                    jax.block_until_ready(metrics)
        tele = metrics.pop("telemetry", None)
        self.last_telemetry = tele
        with t.span("readback", round=rnum):
            rec = {k: float(v) for k, v in metrics.items()}
            tele_rec = (telemetry_dict(tele)
                        if t.enabled and tele is not None else None)
        rec["round"] = self.server.round
        if self.state_store is not None:
            rec.update(state_resident=self.state_store.resident,
                       state_peak=self.state_store.peak_resident,
                       state_spills=self.state_store.spills,
                       state_restores=self.state_store.restores)
        if self.eval_fn is not None:
            with t.span("eval", round=rnum):
                rec.update({k: float(v) for k, v in
                            self.eval_fn(self.server.params).items()})
        if t.enabled:
            t.round_event(rec["round"], rec, telemetry=tele_rec)
        self.history.append(rec)
        return rec

    # ------------------------------------------------------------ accounting

    def comm_bytes_per_round(self) -> int:
        return self.transport.round_bytes(
            self.server.params,
            self.server.theta if self.spec.align else None)
