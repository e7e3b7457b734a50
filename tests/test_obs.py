"""Observability subsystem: jit-pure telemetry (incl. the sync ==
zero-staleness-async bitwise parity), tracer schema + checkpoint
continuity, the tracer's spans on the profiler's clock, the round
program's named scopes, sinks, async drop events, and the BENCH_*.json
document format."""
import dataclasses
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import optim
from repro.core import init_server, make_round_fn, zero_theta
from repro.core.client import LocalRunConfig, client_round
from repro.core.engine import fixed_controller
from repro.checkpoint import CheckpointManager
from repro.fed import (
    AsyncConfig, AsyncFederatedExperiment, FedConfig, LatencyModel,
)
from repro.fed.async_runtime.buffer import make_async_aggregate_fn
from repro.obs import (
    JsonlSink, MemorySink, SCOPES, STALENESS_BINS, StdoutRoundSink,
    Telemetry, Tracer, attach, client_geom_dist, make_bench,
    staleness_histogram, telemetry_dict, validate_bench, validate_event,
    validate_jsonl, write_bench,
)

S, K, D, OUT = 4, 3, 16, 8
KEY = jax.random.key(0)


def _problem():
    W = jax.random.normal(KEY, (D, OUT))
    params = {"w": jnp.zeros((D, OUT))}

    def loss_fn(p, b):
        X, Y = b
        return jnp.mean((X @ p["w"] - Y) ** 2)

    def batches(key):
        X = jax.random.normal(key, (S, K, 8, D))
        return X, X @ W

    return params, loss_fn, batches


def _tele_leaves(t: Telemetry):
    return jax.tree.flatten(t)[0]


# ------------------------------------------------------------- telemetry

def test_telemetry_is_a_jit_pure_pytree():
    t = Telemetry(*(jnp.float32(i) for i in range(7)),
                  client_geom_dist=jnp.arange(S, dtype=jnp.float32),
                  staleness_hist=jnp.zeros(STALENESS_BINS, jnp.int32))
    out = jax.jit(lambda x: x)(t)
    assert isinstance(out, Telemetry)
    for a, b in zip(_tele_leaves(t), _tele_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_round_fn_telemetry_has_no_host_callbacks():
    """The instrumented round must stay a single pure XLA program."""
    params, loss_fn, batches = _problem()
    opt = optim.make("soap")
    rf = make_round_fn(loss_fn, opt, lr=0.05, local_steps=K, beta=0.5,
                       jit=False, telemetry=True)
    server = init_server(params, opt)
    jaxpr = jax.make_jaxpr(
        lambda b, r: rf(server, b, r)[1]["telemetry"])(
            batches(jax.random.key(1)), jax.random.key(2))
    assert "callback" not in str(jaxpr)


def test_sync_round_emits_telemetry():
    params, loss_fn, batches = _problem()
    opt = optim.make("soap")
    rf = make_round_fn(loss_fn, opt, lr=0.05, local_steps=K, beta=0.5,
                       telemetry=True)
    _, metrics = rf(init_server(params, opt), batches(jax.random.key(1)),
                    jax.random.key(2))
    t = metrics["telemetry"]
    assert isinstance(t, Telemetry)
    assert float(t.drift) > 0.0
    assert float(t.beta) == pytest.approx(0.5)
    assert t.client_geom_dist.shape == (S,)
    # synchronous cohort: every client has staleness 0
    np.testing.assert_array_equal(
        np.asarray(t.staleness_hist),
        np.asarray([S] + [0] * (STALENESS_BINS - 1)))
    # host view is JSON-clean
    d = telemetry_dict(t)
    json.dumps(d)
    assert set(d) == {"drift", "norm_drift", "freshness", "beta",
                      "beta_next", "drift_ema", "update_corr_cos",
                      "client_geom_dist", "staleness_hist"}


def test_zero_staleness_async_telemetry_bitwise_matches_sync():
    """The telemetry of a w_i = 1 flush must equal the sync round's
    bitwise — same collect, same arrays (the engine parity contract of
    tests/test_engine.py extended to the diagnostics)."""
    params, loss_fn, batches = _problem()
    opt = optim.make("soap")
    lr, beta = 0.05, 0.5
    b = batches(jax.random.key(1))
    rng = jax.random.key(2)

    rf = make_round_fn(loss_fn, opt, lr=lr, local_steps=K, beta=beta,
                       jit=False, telemetry=True)
    server = init_server(params, opt)
    _, sync_metrics = rf(server, b, rng)
    sync_t = sync_metrics["telemetry"]

    theta0 = zero_theta(opt, params)
    run = LocalRunConfig(lr=lr, local_steps=K, beta=0.0, align=True)
    keys = jax.random.split(rng, S)
    deltas, thetas, _ = jax.vmap(
        lambda bi, ki: client_round(loss_fn, opt, run, params, theta0,
                                    server.g_global, bi, ki,
                                    beta=jnp.float32(beta)))(b, keys)
    flush = make_async_aggregate_fn(lr=lr, local_steps=K, jit=False,
                                    telemetry=True)
    *_, metrics = flush(params, theta0, server.g_global,
                        fixed_controller(beta), deltas, thetas,
                        jnp.ones(S, jnp.float32))
    async_t = metrics["telemetry"]

    for a, c in zip(_tele_leaves(sync_t), _tele_leaves(async_t)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


def test_staleness_histogram():
    h = staleness_histogram(jnp.asarray([0, 0, 1, 3, 99]))
    assert h.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(h), [2, 1, 0, 1, 0, 0, 0, 1])  # 99 clips into last bin
    assert int(h.sum()) == 5


def test_client_geom_dist():
    # no geometry (first-order algorithms): zeros, right shape
    np.testing.assert_array_equal(np.asarray(client_geom_dist(None, 3)),
                                  np.zeros(3))
    # narrow leaves are exact: squared distance to the cohort mean
    thetas = {"a": jnp.asarray([[1.0, 0.0], [0.0, 1.0], [2.0, 2.0]])}
    d = client_geom_dist(thetas, 3)
    mean = np.asarray([1.0, 1.0])
    expect = [np.sum((r - mean) ** 2)
              for r in np.asarray(thetas["a"])]
    np.testing.assert_allclose(np.asarray(d), expect, rtol=1e-6)
    # wide leaves go through the fixed JL sketch: deterministic
    wide = {"a": jax.random.normal(jax.random.key(3), (4, 64))}
    np.testing.assert_array_equal(np.asarray(client_geom_dist(wide, 4)),
                                  np.asarray(client_geom_dist(wide, 4)))


# ---------------------------------------------------------------- tracer

def test_tracer_jsonl_schema(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    t = Tracer(sinks=(JsonlSink(path),))
    t.emit("run_start", runtime="sync")
    with t.span("staging", round=1):
        pass
    t.client_dropped(3, reason="dropout", version=0, sim_time=1.5)
    t.round_event(1, {"loss": 0.5}, telemetry={"drift": 0.1})
    t.sinks[0].close()
    assert validate_jsonl(path) == 4
    lines = [json.loads(x) for x in open(path)]
    assert [e["event"] for e in lines] == ["run_start", "span",
                                          "client_dropped", "round"]
    assert [e["seq"] for e in lines] == [0, 1, 2, 3]
    assert len({e["run_id"] for e in lines}) == 1
    assert lines[1]["phase"] == "staging" and lines[1]["dur_s"] >= 0.0
    assert lines[3]["telemetry"] == {"drift": 0.1}


def test_validate_event_rejects_malformed():
    with pytest.raises(ValueError, match="missing"):
        validate_event({"event": "round", "run_id": "x", "seq": 0})
    with pytest.raises(ValueError, match="unknown trace event"):
        validate_event({"event": "bogus", "run_id": "x", "seq": 0})
    with pytest.raises(ValueError, match="drop reason"):
        validate_event({"event": "client_dropped", "run_id": "x", "seq": 0,
                        "client_id": 1, "reason": "rage_quit", "version": 0})
    with pytest.raises(ValueError, match="empty trace"):
        import tempfile
        with tempfile.NamedTemporaryFile(suffix=".jsonl") as f:
            validate_jsonl(f.name)


def test_tracer_counts_when_disabled_and_state_roundtrips():
    t = Tracer()   # no sinks: counters still advance for checkpoints
    assert not t.enabled
    with t.span("update"):
        pass
    t.round_event(1, {"loss": 1.0})
    t.client_dropped(0, reason="dropout", version=0)  # no-op, no raise
    assert t.spans == 1 and t.rounds == 1 and t.seq == 0
    sink = MemorySink()
    t2 = Tracer.from_state(t.state(), sinks=(sink,))
    assert t2.run_id == t.run_id
    assert (t2.rounds, t2.spans, t2.seq) == (1, 1, 0)
    t2.round_event(2, {"loss": 0.9})
    assert sink.rounds()[0]["round"] == 2
    # empty state -> fresh identity
    assert Tracer.from_state(None).run_id != t.run_id


def test_checkpoint_persists_trace_identity(tmp_path):
    params = {"w": jnp.zeros((4, 4))}
    server = init_server(params, optim.make("sgd"))
    t = Tracer(sinks=(MemorySink(),))
    with t.span("update", round=1):
        pass
    t.round_event(1, {"loss": 1.0})
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(server, telemetry=t.state())
    meta = mgr.restore_meta()
    restored = Tracer.from_state(meta["telemetry"], sinks=(MemorySink(),))
    assert restored.run_id == t.run_id
    assert restored.seq == t.seq and restored.rounds == 1
    # legacy checkpoints (no telemetry key) restore a fresh tracer
    assert Tracer.from_state(meta.get("missing")).seq == 0


def _profiled_spans(tracer, trace_dir):
    """Run three nested/sequential spans under the profiler; returns the
    ``repro.*`` events of the trace's host plane as {name: (start, end)}."""
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(trace_dir))
    with tracer.span("staging", round=1):
        with tracer.span("stage_batches", round=1):
            jnp.ones(4).block_until_ready()
    with tracer.span("readback", round=1):
        pass
    jax.profiler.stop_trace()
    path = next(trace_dir.rglob("*.xplane.pb"))
    host = ProfileData.from_file(str(path)).find_plane_with_name("/host:CPU")
    return {e.name: (e.start_ns, e.start_ns + e.duration_ns)
            for line in host.lines for e in line.events
            if e.name.startswith("repro.")}


def test_enabled_tracer_puts_its_spans_on_the_profiler_clock(tmp_path):
    spans = _profiled_spans(Tracer(sinks=(MemorySink(),)), tmp_path)
    assert sorted(spans) == ["repro.readback", "repro.stage_batches",
                             "repro.staging"]
    (s0, e0), (s1, e1) = spans["repro.staging"], spans["repro.stage_batches"]
    assert s0 <= s1 < e1 <= e0                  # the child nests inside
    assert spans["repro.readback"][0] >= e0


def test_disabled_tracer_puts_nothing_on_the_profiler_clock(tmp_path):
    t = Tracer()
    assert _profiled_spans(t, tmp_path) == {}
    assert t.spans == 3 and t.seq == 0


# ----------------------------------------------------------------- sinks

def test_stdout_sink_is_bitwise_legacy_log_round(capsys):
    rec = {"loss": 0.123456789, "round": 3, "note": None,
           "vec": [1.0, 2.0]}
    StdoutRoundSink().emit({"event": "round", "run_id": "x", "round": 3,
                            "metrics": rec})
    got = capsys.readouterr().out
    legacy = {}
    for k, v in rec.items():   # the pre-sink formatting, verbatim
        try:
            legacy[k] = round(v, 4)
        except TypeError:
            legacy[k] = v
    assert got == f"{legacy}\n"
    StdoutRoundSink().emit({"event": "span", "phase": "eval"})
    assert capsys.readouterr().out == ""


def test_experiment_log_round_routes_through_sink(capsys):
    params, loss_fn, batches = _problem()

    def batch_fn(cid, rng):
        X = jax.random.normal(jax.random.key(cid), (8, D))
        return (X, X @ jax.random.normal(KEY, (D, OUT)))

    fed = FedConfig(algorithm="fedpac_soap", n_clients=4, participation=1.0,
                    rounds=1, local_steps=2)
    from repro.fed import FederatedExperiment
    exp = FederatedExperiment(fed, params, loss_fn, batch_fn)
    rec = exp.run_round()
    capsys.readouterr()
    exp.log_round(rec, 0)
    assert capsys.readouterr().out == \
        f"{ {k: exp.format_metric(v) for k, v in rec.items()} }\n"
    # swapping the sink redirects the same hook
    exp.sink = MemorySink()
    exp.log_round(rec, 0)
    assert exp.sink.rounds()[0]["metrics"] is not None
    assert capsys.readouterr().out == ""


def test_csv_sink_round_rows(tmp_path):
    from repro.obs import CsvSink
    path = str(tmp_path / "rounds.csv")
    with CsvSink(path) as sink:
        sink.emit({"event": "round", "round": 1,
                   "metrics": {"loss": 0.5},
                   "telemetry": {"drift": 0.1,
                                 "staleness_hist": [4, 0]}})
        sink.emit({"event": "span", "phase": "eval"})   # skipped
        sink.emit({"event": "round", "round": 2,
                   "metrics": {"loss": 0.4},
                   "telemetry": {"drift": 0.2,
                                 "staleness_hist": [4, 0]}})
    lines = open(path).read().strip().split("\n")
    assert lines[0] == "round,loss,drift"   # vectors are not columns
    assert lines[1].startswith("1,0.5") and lines[2].startswith("2,0.4")


# --------------------------------------------------- end-to-end (runtimes)

N_CLIENTS = 6


@pytest.fixture(scope="module")
def vision_problem():
    from repro.data import dirichlet_partition, make_image_classification
    from repro.models.vision import classification_loss, cnn_apply, init_cnn
    X, y = make_image_classification(600, image_size=8, n_classes=4, seed=0,
                                     noise=1.0)
    parts = dirichlet_partition(y, N_CLIENTS, 0.2, seed=0)
    params = init_cnn(jax.random.key(0), n_classes=4, width=4, blocks=1)

    def loss_fn(p, batch):
        return classification_loss(cnn_apply(p, batch["x"]), batch["y"])

    def batch_fn(cid, rng):
        idx = rng.choice(parts[cid], size=4)
        return {"x": jnp.asarray(X[idx]), "y": jnp.asarray(y[idx])}

    return params, loss_fn, batch_fn


def _run_traced(vision_problem, seed=0):
    from repro.fed import FederatedExperiment
    params, loss_fn, batch_fn = vision_problem
    fed = FedConfig(algorithm="fedpac_soap", n_clients=N_CLIENTS,
                    participation=0.5, rounds=2, local_steps=2, seed=seed)
    exp = FederatedExperiment(fed, params, loss_fn, batch_fn)
    sink = MemorySink()
    attach(exp, sink)
    exp.run()
    return exp, sink


def test_sync_trace_golden_round(vision_problem):
    """One seeded CNN round: the trace carries schema-valid spans + a
    round event with the full telemetry, deterministically."""
    exp, sink = _run_traced(vision_problem)
    for ev in sink.events:
        validate_event(ev)
    phases = [e["phase"] for e in sink.events if e["event"] == "span"]
    assert phases == ["stage_batches", "staging", "update", "readback"] * 2
    rounds = sink.rounds()
    assert [e["round"] for e in rounds] == [1, 2]
    tele = rounds[0]["telemetry"]
    assert tele["drift"] > 0.0 and tele["beta"] == pytest.approx(0.5)
    assert len(tele["client_geom_dist"]) == 3      # S = 6 * 0.5
    assert sum(tele["staleness_hist"]) == 3
    assert exp.last_telemetry is not None
    assert rounds[0]["metrics"]["loss"] == exp.history[0]["loss"]
    # same seed -> identical telemetry stream (golden determinism)
    _, sink2 = _run_traced(vision_problem)
    assert [e["telemetry"] for e in sink2.rounds()] == \
        [e["telemetry"] for e in rounds]


def test_refresh_routes_recorded_once_per_run(vision_problem):
    """Attaching a trace records how many of SOAP's refresh matrices of each
    shape take each QR route, once, before any round; off the TPU every one
    takes XLA's QR."""
    exp, sink = _run_traced(vision_problem)
    routes = [e for e in sink.events if e["event"] == "refresh_routes"]
    assert len(routes) == 1 and sink.events[0] is routes[0]
    assert routes[0]["optimizer"] == "soap"
    assert routes[0]["routes"] == exp.opt.refresh_routes(exp.server.params)
    assert set(routes[0]["routes"]) == {"xla"} and routes[0]["routes"]["xla"]
    # more rounds record nothing more; detaching records nothing
    sink.events.clear()
    exp.run_round()
    attach(exp)
    assert not [e for e in sink.events if e["event"] == "refresh_routes"]


def test_async_trace_spans_drops_and_staleness(vision_problem):
    params, loss_fn, batch_fn = vision_problem
    fed = FedConfig(algorithm="fedpac_soap", n_clients=N_CLIENTS,
                    participation=1.0, rounds=3, local_steps=2, seed=0,
                    runtime="async")
    acfg = AsyncConfig(buffer_size=2, concurrency=4,
                       latency=LatencyModel(heterogeneity=1.0, jitter=0.5,
                                            dropout=0.3))
    exp = AsyncFederatedExperiment(fed, params, loss_fn, batch_fn,
                                   async_cfg=acfg)
    sink = MemorySink()
    attach(exp, sink)
    exp.run()
    for ev in sink.events:
        validate_event(ev)
    assert [e["event"] for e in sink.events].count("refresh_routes") == 1
    drops = [e for e in sink.events if e["event"] == "client_dropped"]
    # every silent counter bump is now an explicit trace event
    assert len(drops) == exp.total_dropped + exp.total_discarded
    for e in drops:
        assert e["reason"] in ("dropout", "max_staleness")
        assert "sim_time" in e
    phases = {e["phase"] for e in sink.events if e["event"] == "span"}
    assert {"staging", "local_update", "flush"} <= phases
    rounds = sink.rounds()
    assert len(rounds) == 3 and all("sim_time" in e for e in rounds)
    hist = rounds[-1]["telemetry"]["staleness_hist"]
    assert sum(hist) == acfg.buffer_size   # buffer's staleness, binned


def test_round_program_names_every_scope():
    """The compiled round program of a small FedPAC-SOAP experiment with a
    qblock Theta upload carries each name of ``SCOPES`` in its ops'
    ``op_name`` metadata, which is what a device trace reports."""
    from repro.api import build_experiment
    exp = build_experiment("fedpac_soap", scenario="cifar_like_cnn",
                           n_clients=4, participation=0.5, local_steps=2,
                           batch_size=4, theta_codec="qblock", seed=0)
    cohort = exp._sample_cohort()
    batches = exp._stage_batches(cohort)

    def program(params, batches, key):
        server = dataclasses.replace(exp.server, params=params)
        server, cstate, metrics = exp.round_fn(
            server, exp.client_state, jnp.asarray(cohort), batches, key)
        return server.params, server.theta, server.g_global, cstate, metrics

    hlo = jax.jit(program).lower(exp.server.params, batches,
                                 jax.random.key(0)).compile().as_text()
    op_names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in SCOPES:
        assert any(re.search(rf"(^|[/(]){scope}($|[/)])", n)
                   for n in op_names), scope


# ------------------------------------------------------------ BENCH docs

def test_bench_write_read_roundtrip(tmp_path):
    rows = [{"name": "exec_vmap_S4", "us_per_call": 12.5,
             "derived": {"loss": 0.9, "backend": "vmap"}},
            {"name": "exec_agree_S4", "us_per_call": 0.0,
             "derived": {"max_dev": 0.0}}]
    path = str(tmp_path / "BENCH_executor.json")
    doc = write_bench(path, "executor", rows, config={"quick": True})
    validate_bench(doc)
    from repro.obs import read_bench
    got = read_bench(path)
    assert got["bench"] == "executor" and got["config"] == {"quick": True}
    assert got["rows"] == rows


@pytest.mark.parametrize("mutate, match", [
    (lambda d: d.pop("rows"), "missing"),
    (lambda d: d.update(schema_version=99), "schema_version"),
    (lambda d: d.update(rows=[]), "non-empty"),
    (lambda d: d["rows"].append(dict(d["rows"][0])), "duplicate"),
    (lambda d: d["rows"][0].update(us_per_call="fast"), "numeric"),
    (lambda d: d["rows"][0]["derived"].update(bad=[1, 2]), "scalar"),
])
def test_bench_validation_rejects(mutate, match):
    doc = make_bench("executor",
                     [{"name": "a", "us_per_call": 1.0,
                       "derived": {"x": 1}}])
    mutate(doc)
    with pytest.raises(ValueError, match=match):
        validate_bench(doc)
