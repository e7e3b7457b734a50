"""First-class algorithm API: the ``AlgorithmSpec`` registry and the one
uniform round path every algorithm runs through.

An algorithm is *data*, not a string: a frozen ``AlgorithmSpec`` declaring
its local optimizer, alignment/correction policy, beta policy (including
FedCM's pinned beta — the rule lives with the algorithm, not in runtime
branches), upload codec, per-client persistent state, aggregation mixing
weights, and comm accounting.  Both runtimes consume specs through one
driver signature

    round_fn(server, client_state, cohort, batches, rng)
        -> (server, client_state, metrics)

so SCAFFOLD's control variates (``core.scaffold``) and the FedPM-style
preconditioned-mixing aggregation (``core.fedpm``) flow through exactly the
same engine path as FedPAC — no special-cased forks, no dual signatures.

Registering a new algorithm takes ~10 lines and zero runtime changes::

    from repro.core.algorithms import AlgorithmSpec, register
    register(AlgorithmSpec(name="my_alg", optimizer="soap",
                           align=True, correct=True))

Legacy strings (``fedpac_soap_light``, ...) keep working: ``resolve`` maps
every name from the paper's tables onto a registered spec (``*_light`` is a
derived variant with the SVD upload codec).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import optim
from repro.core.client import LocalRunConfig, client_round
from repro.core.engine import (
    AggregationConfig, BETA_MAX_AUTO, ExecutorConfig, advance_server,
    aggregate, aggregate_wire, make_cohort_executor, make_controller,
    update_controller,
)
from repro.core.server import ServerState
from repro.core import transport as T
from repro.optim.api import LocalOptimizer
from repro.utils import hw


class UnknownAlgorithmError(ValueError):
    """Name resolves to no registered ``AlgorithmSpec``."""


class DuplicateAlgorithmError(ValueError):
    """``register`` called twice for the same name without overwrite."""


@dataclasses.dataclass(frozen=True)
class ClientStateSpec:
    """Unified per-client persistent-state protocol.

    Algorithms that carry state across rounds (SCAFFOLD's control variates)
    declare it here; the engine threads it through the one round path.
    State is kept *stacked* with a leading (N,) client axis so cohorts
    gather it inside jit and it shards over the mesh in distributed runs.

      init(params, n_clients)              -> stacked state pytree
      client_view(state, cid)              -> what one client reads
      server_update(state, cohort, outs,
                    n_clients)             -> new state (scatter + globals)

    ``outs`` is the cohort-stacked third element of the local update's
    return value (None for stateless algorithms).

    ``client_export``/``client_import`` are the sparse-population spill
    hooks: export one client's *private row* out of the stacked state /
    graft a row back in.  They default to the generic stacked-leaf slice
    (``leaf[cid]`` / ``leaf.at[cid].set(row)``), which is correct whenever
    every leaf carries the leading (N,) client axis (error-feedback
    residuals do).  States that mix per-client rows with shared globals
    (SCAFFOLD's ``c_global``) must override them so only the private part
    travels to the checkpoint store — use the module helpers
    ``state_export``/``state_import`` rather than calling these directly.
    """
    init: Callable[[Any, int], Any]
    client_view: Callable[[Any, Any], Any]
    server_update: Callable[[Any, Any, Any, int], Any]
    client_export: Optional[Callable[[Any, int], Any]] = None
    client_import: Optional[Callable[[Any, int, Any], Any]] = None
    # batched import: graft many rows (stacked along a leading axis aligned
    # with the id array) in ONE scatter.  Functional per-client .at[].set
    # copies the whole stacked state each call — O(cohort x budget) per
    # acquire — so the population store always imports through
    # ``state_import_many``; override this alongside ``client_import``
    client_import_many: Optional[Callable[[Any, Any, Any], Any]] = None


@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    """One federated algorithm, declaratively.

    local_update: factory ``(spec, loss_fn, opt, run) -> local_fn`` with
      ``local_fn(params, theta, g_global, *, beta, view, batch_i, key_i)
      -> (delta, theta_out_or_None, client_out_or_None, loss)``;
      None selects the standard ``core.client.client_round`` path.
    mixing: optional per-client aggregation weights
      ``(deltas, thetas) -> (S,)`` fed into the engine's weighted delta
      mean (e.g. ``engine.aggregation.precond_mixing_weights``).
    pinned_beta: algorithm-mandated correction strength overriding the
      user's ``FedConfig.beta`` (FedCM's (1 - alpha) = 0.9).
    """
    name: str
    optimizer: str = "sgd"
    align: bool = False
    correct: bool = False
    pinned_beta: Optional[float] = None
    upload: str = "dense"               # Theta codec spec (transport registry;
    #                                     "svd" is the legacy lowrank alias)
    delta_upload: str = "dense"         # delta codec spec (transport registry)
    local_update: Optional[Callable] = None
    client_state: Optional[ClientStateSpec] = None
    mixing: Optional[Callable] = None
    default_lr: Optional[float] = None  # overrides the optimizer's table lr
    description: str = ""

    def __post_init__(self):
        T.validate_codec_spec(self.upload)
        T.validate_codec_spec(self.delta_upload)

    # ------------------------------------------------------------ policies

    def resolve_beta(self, requested: Union[float, str]):
        """The one beta rule: no correction => 0; pinned (FedCM and its
        variants) wins; "auto" passes through to the adaptive controller."""
        if not self.correct:
            return 0.0
        if self.pinned_beta is not None:
            return float(self.pinned_beta)
        if requested == "auto":
            return "auto"
        return float(requested)

    def make_optimizer(self, **opt_kwargs) -> LocalOptimizer:
        return optim.make(self.optimizer, **opt_kwargs)

    def make_transport(self, *, rank: int = 8, block: int = 128,
                       sketch_iters: int = 2, delta_codec=None,
                       theta_codec=None, error_feedback: bool = True,
                       use_pallas: Optional[bool] = None,
                       interpret: Optional[bool] = None,
                       wire_dtype: str = "f32") -> T.Transport:
        """Resolve this spec's wire policy (``delta_codec``/``theta_codec``
        override the spec's declared codec specs, e.g. from FedConfig).
        ``use_pallas=None``/``interpret=None`` resolve through the shared
        backend auto rule (``repro.utils.hw``): real Pallas kernels on
        TPU, the jnp reference/interpreter everywhere else.
        ``wire_dtype`` caps floating payload dtypes on the wire
        ("f32" native | "bf16")."""
        cfg = T.TransportConfig(rank=rank, block=block,
                                sketch_iters=sketch_iters,
                                use_pallas=hw.resolve_use_pallas(use_pallas),
                                interpret=hw.resolve_interpret(interpret),
                                wire_dtype=wire_dtype)
        return T.Transport(
            delta=T.resolve_codec(
                self.delta_upload if delta_codec is None else delta_codec,
                cfg),
            theta=T.resolve_codec(
                self.upload if theta_codec is None else theta_codec, cfg),
            error_feedback=error_feedback)

    def init_client_state(self, params, n_clients: int):
        """Fresh persistent state (None for stateless algorithms)."""
        if self.client_state is None:
            return None
        return self.client_state.init(params, n_clients)

    def comm_bytes(self, params, theta, *, svd_rank: Optional[int] = None
                   ) -> int:
        """Per-client upload bytes for one round (Table 6 accounting).

        Deprecated shim: measured from the wire messages this spec's
        default transport encodes (``transport.wire_bytes``)."""
        transport = self.make_transport(rank=svd_rank or 8)
        return transport.round_bytes(params, theta if self.align else None)

    # ------------------------------------------------------------ variants

    def light(self) -> "AlgorithmSpec":
        """Derived ``<name>_light`` variant: rank-r SVD Theta upload."""
        return dataclasses.replace(self, name=f"{self.name}_light",
                                   upload="svd")


# ----------------------------------------------------------------- registry

_REGISTRY: dict[str, AlgorithmSpec] = {}
_BUILTINS_LOADED = False


def _ensure_builtins():
    """Import the modules that register built-in specs (idempotent).

    SCAFFOLD and FedPM live in their own modules and self-register on
    import; loading them lazily keeps this module import-cycle-free.
    """
    global _BUILTINS_LOADED
    if _BUILTINS_LOADED:
        return
    from repro.core import scaffold, fedpm  # noqa: F401  (self-registering)
    _BUILTINS_LOADED = True  # only after the imports succeed: a transient
    #                          failure must not poison the registry


def register(spec: AlgorithmSpec, *, overwrite: bool = False) -> AlgorithmSpec:
    """Add ``spec`` to the registry; returns it for chaining."""
    if not isinstance(spec, AlgorithmSpec):
        raise TypeError(f"register wants an AlgorithmSpec, got {type(spec)}")
    if spec.optimizer not in optim.available():
        raise ValueError(
            f"spec {spec.name!r} names unknown optimizer {spec.optimizer!r} "
            f"(want one of {optim.available()})")
    if spec.name in _REGISTRY and not overwrite:
        raise DuplicateAlgorithmError(
            f"algorithm {spec.name!r} is already registered "
            "(pass overwrite=True to replace it)")
    _REGISTRY[spec.name] = spec
    return spec


def registered() -> tuple:
    """Sorted names of all registered algorithms."""
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def get(name: str) -> AlgorithmSpec:
    _ensure_builtins()
    if name in _REGISTRY:
        return _REGISTRY[name]
    if name.endswith("_light"):
        base = name[: -len("_light")]
        if base in _REGISTRY:
            return _REGISTRY[base].light()
    raise UnknownAlgorithmError(
        f"unknown algorithm {name!r}: registered specs are "
        f"{', '.join(registered())} (append '_light' for the rank-r SVD "
        "Theta upload); add new ones via repro.core.algorithms.register")


def resolve(spec_or_name: Union[str, AlgorithmSpec]) -> AlgorithmSpec:
    """Spec passes through; strings (incl. every legacy paper-table name)
    resolve against the registry."""
    if isinstance(spec_or_name, AlgorithmSpec):
        return spec_or_name
    return get(str(spec_or_name))


# -------------------------------------------------------- uniform round path

def zero_theta(opt: LocalOptimizer, params):
    """Fresh (zero) preconditioner pytree for ``opt`` on ``params``.

    Round 0 has no global reference yet; both runtimes align to this."""
    state = jax.eval_shape(opt.init, params)
    theta_shape = jax.eval_shape(lambda s: opt.get_precond(s), state)
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), theta_shape)


def make_local_update(spec: AlgorithmSpec, loss_fn: Callable,
                      opt: LocalOptimizer, run: LocalRunConfig) -> Callable:
    """The spec's local update; defaults to the standard ``client_round``."""
    if spec.local_update is not None:
        return spec.local_update(spec, loss_fn, opt, run)

    def local_fn(params, theta, g_global, *, beta, view, batch_i, key_i):
        del view  # stateless
        delta, theta_out, loss = client_round(
            loss_fn, opt, run, params, theta, g_global, batch_i, key_i,
            beta=beta)
        return delta, theta_out, None, loss

    return local_fn


def make_wire_client_step(spec: AlgorithmSpec, local_fn: Callable,
                          transport: Optional[T.Transport],
                          state_proto: Optional[ClientStateSpec], *,
                          fused: bool) -> Callable:
    """One client's round, from state view to wire message.

    ``client_step(params, theta, g_global, beta, cstate, cid, batch_i,
    key_i) -> (dchan, tmsg, out, loss)`` — the body ``build_round_fn``
    vmaps over the cohort, factored out so the chunk-streaming pipeline
    (``fed.pipeline``) traces the *identical* per-client computation
    (parity between the two paths is bitwise, not just numeric).

    The client-side encode is the wire boundary: what leaves the client IS
    the wire msg.  The fused server path reduces wire messages directly,
    so the decoded tree stays a client-local transient (it still forms the
    EF residual); only the decode-then-aggregate fallback (``fused=False``,
    mixing hooks) ships it server-side alongside the message.
    """
    ef_active = transport is not None and transport.feedback_active
    has_algo_state = spec.client_state is not None
    encode_theta = transport is not None and spec.align

    def client_step(params, theta, g_global, beta, cstate, cid, batch_i,
                    key_i):
        view = (state_proto.client_view(cstate, cid)
                if state_proto is not None else None)
        if ef_active:
            algo_view, residual = view if has_algo_state else (None, view)
        else:
            algo_view, residual = view, None
        delta, theta_out, algo_out, loss = local_fn(
            params, theta, g_global, beta=beta, view=algo_view,
            batch_i=batch_i, key_i=key_i)
        if transport is None:
            return delta, theta_out, algo_out, loss
        with jax.named_scope("upload_encode"):
            dmsg, decoded, new_residual = T.encode_with_feedback(
                transport.delta, delta, residual)
            tmsg = (transport.theta.encode(theta_out) if encode_theta
                    else theta_out)
        dchan = (dmsg, decoded) if (ef_active and not fused) else dmsg
        if ef_active:
            out = ((algo_out, new_residual) if has_algo_state
                   else new_residual)
        else:
            out = algo_out
        return dchan, tmsg, out, loss

    return client_step


def state_export(proto: ClientStateSpec, state, cid):
    """One client's private state row (the unit the sparse population store
    spills to the checkpoint store).  Generic stacked-leaf slice unless the
    spec overrides ``client_export``."""
    if proto.client_export is not None:
        return proto.client_export(state, cid)
    return jax.tree.map(lambda x: x[cid], state)


def state_import(proto: ClientStateSpec, state, cid, row):
    """Graft a private row (from ``state_export`` or a spill file) back into
    the stacked state at ``cid``."""
    if proto.client_import is not None:
        return proto.client_import(state, cid, row)
    return jax.tree.map(lambda x, r: x.at[cid].set(r), state, row)


def state_import_many(proto: ClientStateSpec, state, cids, rows):
    """Graft many private rows in one scatter (``rows`` stacked along a
    leading axis aligned with ``cids``).

    This is the population store's import path: a single functional
    ``.at[ids].set`` costs one full-state copy total, where per-client
    ``state_import`` would copy the whole stacked state once *per client*
    (O(cohort x budget) — quadratic in the cohort when the budget tracks
    it).  Values are identical to sequential imports at distinct ids.
    Specs that override ``client_import`` without a batched variant fall
    back to the sequential path."""
    if proto.client_import_many is not None:
        return proto.client_import_many(state, cids, rows)
    if proto.client_import is not None:
        # sequential fallback: host ids only (specs that want jit-traced
        # grafts — the pipeline's in-step restore — override
        # ``client_import_many``)
        for i, cid in enumerate(np.asarray(cids)):
            state = proto.client_import(
                state, int(cid), jax.tree.map(lambda x: x[i], rows))
        return state
    ids = jnp.asarray(cids)   # may be traced: the pipeline grafts in-jit
    return jax.tree.map(lambda x, r: x.at[ids].set(r), state, rows)


# error-feedback residuals, declared through the same per-client state
# protocol as algorithm state (SCAFFOLD's variates): the engine gathers the
# cohort's residuals inside jit and scatters the refreshed ones back.
EF_STATE = ClientStateSpec(init=T.ef_init, client_view=T.ef_view,
                           server_update=lambda s, cohort, outs, n:
                           T.ef_scatter(s, cohort, outs))


def _compose_state_specs(algo: ClientStateSpec,
                         ef: ClientStateSpec) -> ClientStateSpec:
    """Pair algorithm state with transport (EF) state: one protocol, two
    independently-threaded slots."""
    return ClientStateSpec(
        init=lambda p, n: (algo.init(p, n), ef.init(p, n)),
        client_view=lambda s, cid: (algo.client_view(s[0], cid),
                                    ef.client_view(s[1], cid)),
        server_update=lambda s, cohort, outs, n: (
            algo.server_update(s[0], cohort, outs[0], n),
            ef.server_update(s[1], cohort, outs[1], n)),
        client_export=lambda s, cid: (state_export(algo, s[0], cid),
                                      state_export(ef, s[1], cid)),
        client_import=lambda s, cid, row: (
            state_import(algo, s[0], cid, row[0]),
            state_import(ef, s[1], cid, row[1])),
        client_import_many=lambda s, cids, rows: (
            state_import_many(algo, s[0], cids, rows[0]),
            state_import_many(ef, s[1], cids, rows[1])))


def round_client_state_spec(spec: AlgorithmSpec,
                            transport: Optional[T.Transport] = None
                            ) -> Optional[ClientStateSpec]:
    """The full per-client state protocol of one run: the algorithm's
    declared state, the transport's error-feedback residuals (lossy delta
    codec only), their composition, or None."""
    ef = EF_STATE if (transport is not None
                      and transport.feedback_active) else None
    algo = spec.client_state
    if ef is None:
        return algo
    if algo is None:
        return ef
    return _compose_state_specs(algo, ef)


def init_round_client_state(spec: AlgorithmSpec, transport, params,
                            n_clients: int):
    """Fresh state matching ``round_client_state_spec`` (None if stateless)."""
    proto = round_client_state_spec(spec, transport)
    return proto.init(params, n_clients) if proto is not None else None


def build_round_fn(
    spec: AlgorithmSpec,
    loss_fn: Callable,
    opt: LocalOptimizer,
    *,
    lr: float,
    local_steps: int,
    beta: Union[float, str] = 0.5,
    hessian_freq: int = 10,
    server_lr: float = 1.0,
    compress_fn: Optional[Callable] = None,
    transport: Optional[T.Transport] = None,
    beta_max: float = BETA_MAX_AUTO,
    drift_ema: float = 1.0,
    executor: Optional[ExecutorConfig] = None,
    n_clients: Optional[int] = None,
    jit: bool = True,
    telemetry: bool = False,
):
    """The one round implementation, for every registered algorithm.

    Returns ``driver(server, client_state, cohort, batches, rng) ->
    (server, client_state, metrics)`` — the uniform signature both runtimes
    use (``client_state`` is None for stateless algorithms).  batches carry
    leading (S, K, ...) axes; ``cohort`` is the (S,) array of client ids
    (persistent state is gathered/scattered by it inside jit).

    ``transport`` routes the uploads through wire-true codecs: each client
    encodes its delta (error-compensated for lossy codecs) and, for
    aligned algorithms, its Theta; the server runs the *fused* flush
    (``engine.aggregate_wire``) — encoded uploads accumulate straight into
    the weighted sums via ``Codec.accumulate``, never materializing the
    decoded per-client stack — and reports the measured ``upload_bytes``.
    Algorithms with a ``mixing`` hook (which consumes the decoded cohort)
    fall back to decode-then-``aggregate``.  ``compress_fn`` is the legacy
    stacked Theta round-trip (exclusive with ``transport``); None for both
    is the plain dense path.

    ``telemetry=True`` additionally computes the jit-pure ``Telemetry``
    diagnostics (``repro.obs.telemetry``) inside the round and returns the
    pytree under ``metrics["telemetry"]`` — the same ``collect`` the async
    flush runs, so sync and zero-staleness-async telemetry agree bitwise.
    """
    if transport is not None and compress_fn is not None:
        raise ValueError("pass either transport or the legacy compress_fn, "
                         "not both")
    state_proto = round_client_state_spec(spec, transport)
    ef_active = transport is not None and transport.feedback_active
    has_algo_state = spec.client_state is not None
    if state_proto is not None and n_clients is None:
        raise ValueError(
            f"algorithm {spec.name!r} carries per-client state "
            f"({'error-feedback residuals' if not has_algo_state else 'declared algorithm state'}); "
            "build_round_fn needs n_clients")
    encode_theta = transport is not None and spec.align
    # the fused wire path needs no decoded cohort; mixing hooks consume
    # the decoded stacks, so they keep the decode-then-aggregate path
    fused = transport is not None and spec.mixing is None
    default_ctrl = make_controller(beta, correct=spec.correct,
                                   beta_max=beta_max, ema=drift_ema)
    run = LocalRunConfig(lr=lr, local_steps=local_steps, beta=0.0,
                         hessian_freq=hessian_freq, align=spec.align)
    agg_cfg = AggregationConfig(lr=lr, local_steps=local_steps,
                                server_lr=server_lr, align=spec.align)
    cohort_exec = make_cohort_executor(executor)
    local_fn = make_local_update(spec, loss_fn, opt, run)
    client_step = make_wire_client_step(spec, local_fn, transport,
                                        state_proto, fused=fused)
    # wire accounting is static shape math: captured at trace time and
    # reported host-side as an exact int (f32 metrics would round above
    # 2^24 bytes)
    wire_cell = {}

    def round_fn(params, theta, g_global, ctrl, cstate, cohort, batches, rng):
        s = jax.tree.leaves(batches)[0].shape[0]
        # rng is either one round key (legacy: split S ways) or an already
        # stacked (S,) vector of per-client fold_in-derived keys (population
        # runs, where a client's stream must not depend on cohort makeup).
        # Typed keys make this a static trace-time branch: scalar key
        # ndim == 0, stacked ndim == 1.
        if jnp.issubdtype(rng.dtype, jax.dtypes.prng_key) and rng.ndim == 1:
            keys = rng
        else:
            keys = jax.random.split(rng, s)

        def one_client(cid, batch_i, key_i):
            return client_step(params, theta, g_global, ctrl.beta, cstate,
                               cid, batch_i, key_i)

        deltas, thetas, outs, losses = cohort_exec(
            one_client, cohort, batches, keys)
        step = None
        weights = jnp.ones((s,), jnp.float32)
        with jax.named_scope("flush"):
            if fused:
                # fused wire path: the stacked messages reduce straight into
                # the weighted sums (Codec.accumulate); byte counts are static
                # shape math over those same structures, recorded as the exact
                # total + cohort size (no truncating division)
                up_bytes = T.wire_bytes(deltas)
                if encode_theta:
                    up_bytes += T.wire_bytes(thetas)
                wire_cell["total"] = up_bytes
                wire_cell["cohort"] = s
                new_params, new_theta, new_g, agg, aux = aggregate_wire(
                    params, theta, g_global, deltas, weights, agg_cfg,
                    transport, tmsgs=thetas if encode_theta else None,
                    thetas=None if encode_theta else thetas,
                    need_thetas=telemetry)
                deltas, thetas, step = None, aux["thetas"], aux["step"]
            else:
                if transport is not None:
                    # decode-then-aggregate fallback: mixing hooks consume the
                    # decoded cohort, so it must materialize here
                    if ef_active:
                        dmsgs, deltas = deltas
                        up_bytes = T.wire_bytes(dmsgs)
                    else:
                        up_bytes = T.wire_bytes(deltas)
                        deltas = jax.vmap(transport.delta.decode)(deltas)
                    if encode_theta:
                        up_bytes += T.wire_bytes(thetas)
                        thetas = jax.vmap(transport.theta.decode)(thetas)
                    wire_cell["total"] = up_bytes
                    wire_cell["cohort"] = s
                elif compress_fn is not None and thetas is not None:
                    # legacy path: clients upload compressed Theta; server
                    # aggregates the decoded reconstruction (Table 6 trade-off)
                    thetas = compress_fn(thetas)
                if spec.mixing is not None:
                    weights = spec.mixing(deltas, thetas)
                new_params, new_theta, new_g, agg = aggregate(
                    params, theta, g_global, deltas, thetas, weights, agg_cfg)
        with jax.named_scope("server_update"):
            new_cstate = (state_proto.server_update(cstate, cohort, outs,
                                                    n_clients)
                          if state_proto is not None else cstate)
            new_ctrl = update_controller(ctrl, agg["norm_drift"],
                                         agg["freshness"])
            metrics = dict(agg, loss=jnp.mean(losses), beta=ctrl.beta)
            if telemetry:
                from repro.obs import telemetry as obs_telemetry
                metrics["telemetry"] = obs_telemetry.collect(
                    deltas=deltas, step=step, thetas=thetas,
                    weights=weights, g_global=g_global, ctrl=ctrl,
                    new_ctrl=new_ctrl, agg_metrics=agg)
        return new_params, new_theta, new_g, new_ctrl, new_cstate, metrics

    if jit:
        round_fn = jax.jit(round_fn)

    def driver(server: ServerState, cstate, cohort, batches, rng):
        ctrl = server.geom if server.geom is not None else default_ctrl
        theta = server.theta
        if spec.align and theta is None:
            # round 0: no reference yet -> align to the fresh (zero) state.
            theta = zero_theta(opt, server.params)
        p, th, g, new_ctrl, new_cstate, metrics = round_fn(
            server.params, theta, server.g_global, ctrl, cstate, cohort,
            batches, rng)
        if transport is not None:
            # exact host-side ints captured at trace time (never lossy f32
            # device scalars); upload_bytes keeps its historical per-client
            # meaning while the untruncated total rides along
            total, cohort = wire_cell["total"], wire_cell["cohort"]
            metrics = dict(metrics, upload_bytes=total // cohort,
                           upload_total_bytes=total, cohort_size=cohort)
        new_server = advance_server(server, p, th, g, geom=new_ctrl,
                                    aligned=spec.align)
        return new_server, new_cstate, metrics

    return driver


# ------------------------------------------------------- built-in algorithms

def _register_stateless_builtins():
    register(AlgorithmSpec(
        name="fedavg", optimizer="sgd",
        description="SGD locally, parameter averaging"))
    register(AlgorithmSpec(
        name="fedcm", optimizer="sgd", correct=True, pinned_beta=0.9,
        description="client momentum: correction-only SGD, beta pinned to "
                    "(1 - alpha) = 0.9"))
    for opt_name in optim.available():
        register(AlgorithmSpec(
            name=f"local_{opt_name}", optimizer=opt_name,
            description=f"FedSOA (Alg. 1) with {opt_name}: fresh local "
                        "state each round, parameter averaging"))
        register(AlgorithmSpec(
            name=f"fedpac_{opt_name}", optimizer=opt_name, align=True,
            correct=True,
            description=f"FedPAC (Alg. 2) with {opt_name}: preconditioner "
                        "Alignment + direction Correction"))
        register(AlgorithmSpec(
            name=f"align_only_{opt_name}", optimizer=opt_name, align=True,
            description="Table 5 ablation: Alignment without Correction"))
        register(AlgorithmSpec(
            name=f"correct_only_{opt_name}", optimizer=opt_name,
            correct=True,
            description="Table 5 ablation: Correction without Alignment"))


_register_stateless_builtins()
