"""Continuous-traffic runtime: golden scheduler traces, saturating-trace
parity with the round-shaped async runtime, churn/eviction, hourly
availability traces, mid-stream checkpoint/rollback in a fresh process,
hot-swap, and the sharded executor on a forced multi-device mesh."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.api import (
    AsyncConfig, ChurnConfig, TrafficConfig, build_experiment,
)
from repro.fed.async_runtime.latency import LatencyModel
from repro.fed.async_runtime.scheduler import SimScheduler
from repro.fed.population import (
    AvailabilitySampler, ClientPopulation, hourly_availability,
    load_hourly_trace,
)
from repro.fed.population.state import ClientStateStore, DenseClientStore
from repro.fed.traffic import (
    BurstyRate, ConstantRate, DiurnalRate, Membership, PiecewiseRate,
    run_ab, time_to_quality,
)
from repro.obs import MemorySink, attach

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

# ------------------------------------------------------------------ fixtures


def _mlp_problem(n_clients=8, seed=0):
    """Tiny 2-layer MLP bundle (NOT single-layer {'w','b'}: tiny params
    give all-None SOAP Theta, breaking fedpac_soap wire decode)."""
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(240, 8)).astype(np.float32)
    y = rng.integers(0, 3, size=240).astype(np.int32)
    parts = np.array_split(np.arange(240), n_clients)
    params = {
        "w1": jnp.asarray(rng.normal(size=(8, 16)) * 0.1, jnp.float32),
        "b1": jnp.zeros((16,), jnp.float32),
        "w2": jnp.asarray(rng.normal(size=(16, 3)) * 0.1, jnp.float32),
        "b2": jnp.zeros((3,), jnp.float32),
    }

    def loss_fn(p, batch):
        xb, yb = batch
        h = jnp.tanh(xb @ p["w1"] + p["b1"])
        logp = jax.nn.log_softmax(h @ p["w2"] + p["b2"])
        return -jnp.mean(jnp.take_along_axis(logp, yb[:, None], 1))

    def client_batch_fn(cid, rng_):
        idx = parts[cid % n_clients]
        sel = rng_.choice(idx, size=32)
        return jnp.asarray(X[sel]), jnp.asarray(y[sel])

    def eval_fn(p):
        h = jnp.tanh(X @ p["w1"] + p["b1"])
        acc = jnp.mean(jnp.argmax(h @ p["w2"] + p["b2"], -1) == y)
        return {"acc": float(acc)}

    return dict(params=params, loss_fn=loss_fn,
                client_batch_fn=client_batch_fn, eval_fn=eval_fn)


@pytest.fixture(scope="module")
def problem():
    return _mlp_problem()


ACFG = dict(buffer_size=3, concurrency=4)


# ------------------------------------------ satellite: sparse golden traces

# Event streams captured from the dense-array scheduler implementation
# before the sparse-dict refactor: (time rounded to 1e-10, seq, client_id,
# version, dropped) under seed 7, LatencyModel(heterogeneity=1.0,
# jitter=0.5, dropout=0.2), concurrency 4, fill(0) then 11 x
# [next_completion; fill(v)].  The sparse bookkeeping must reproduce them
# bitwise.
_GOLDEN_DENSE = [
    (0.1849445715, 3, 3, 0, False), (0.4788669236, 4, 4, 1, False),
    (0.872413376, 0, 1, 0, False), (0.9768364724, 5, 4, 2, False),
    (1.1202894693, 1, 6, 0, False), (1.3148618456, 7, 1, 4, False),
    (1.7559337257, 9, 4, 6, True), (1.8280339791, 6, 0, 3, False),
    (2.1121211088, 8, 6, 5, False), (2.1843801283, 11, 3, 8, False),
    (2.3123729954, 10, 5, 7, False),
]
_GOLDEN_POP = [
    (0.2218409762, 3, 591, 0, False), (0.2560703027, 0, 816, 0, False),
    (0.5192363124, 2, 882, 0, False), (0.6904145788, 6, 195, 3, False),
    (0.9751509702, 4, 967, 1, True), (1.1028231546, 5, 251, 2, False),
    (1.4313807391, 9, 328, 6, False), (1.8097268758, 1, 893, 0, False),
    (2.3299991907, 8, 635, 5, False), (2.7773877936, 12, 67, 9, True),
    (2.8587555338, 11, 300, 8, False),
]


def _drain(sched, n=11):
    sched.fill(0)
    out = []
    for v in range(1, n + 1):
        ev = sched.next_completion()
        out.append((round(ev.time, 10), ev.seq, ev.client_id, ev.version,
                    ev.dropped))
        sched.fill(v)
    return out


def test_scheduler_golden_dense():
    lat = LatencyModel(heterogeneity=1.0, jitter=0.5, dropout=0.2)
    assert _drain(SimScheduler(lat, 8, 4, seed=7)) == _GOLDEN_DENSE


def test_scheduler_golden_population():
    lat = LatencyModel(heterogeneity=1.0, jitter=0.5, dropout=0.2)
    sched = SimScheduler(lat, 0, 4, seed=7,
                         population=ClientPopulation(1000, seed=7))
    assert _drain(sched) == _GOLDEN_POP


def test_scheduler_void_and_state_roundtrip():
    lat = LatencyModel(heterogeneity=1.0, jitter=0.5, dropout=0.2)
    sched = SimScheduler(lat, 8, 4, seed=3)
    sched.fill(0)
    assert sched.peek_time() is not None
    cid = next(iter(sched._live_seq))
    seq = sched.void(cid)
    assert seq == sched._live_seq[cid]
    assert sched.void(999) is None
    st = sched.state()
    # voided mark survives a state round-trip
    sched2 = SimScheduler(lat, 8, 4, seed=3)
    sched2.load_state(st)
    sched2.restore_events(list(sched._heap))
    while True:
        ev = sched2.next_completion()
        if ev.client_id == cid:
            assert sched2.consume_voided(ev)
            break
        assert not sched2.consume_voided(ev)


# --------------------------------------------- acceptance: saturating parity


def test_saturating_trace_reproduces_round_shaped_async(problem):
    """Zero churn + ConstantRate(inf) + count policy == the legacy
    round-shaped async runtime, metric for metric."""
    kw = dict(problem, n_clients=8, rounds=4, seed=11)
    legacy = build_experiment("fedpac_soap", async_cfg=AsyncConfig(**ACFG),
                              **kw)
    hist_legacy = legacy.run()
    traffic = build_experiment(
        "fedpac_soap", async_cfg=AsyncConfig(**ACFG),
        traffic=TrafficConfig(trace="constant",
                              trace_kwargs={"rate": float("inf")}), **kw)
    hist_traffic = traffic.run()
    assert len(hist_legacy) == len(hist_traffic) == 4
    for a, b in zip(hist_legacy, hist_traffic):
        assert set(a) == set(b)
        for k in a:
            assert a[k] == b[k], (k, a[k], b[k])


# ----------------------------------------------------------- arrival traces


def test_trace_processes_deterministic_and_checkpointable():
    for proc in (ConstantRate(3.0, seed=5),
                 DiurnalRate(4.0, amplitude=0.7, period=6.0, seed=5),
                 BurstyRate(2.0, jump=0.5, decay=1.0, seed=5),
                 PiecewiseRate([1.0, 5.0, 0.5], bin_width=2.0, seed=5)):
        st = proc.state()
        t, times = 0.0, []
        for _ in range(20):
            t = proc.next_arrival(t)
            proc.notify_arrival(t)
            times.append(t)
        assert times == sorted(times)
        proc.load_state(st)
        t2, times2 = 0.0, []
        for _ in range(20):
            t2 = proc.next_arrival(t2)
            proc.notify_arrival(t2)
            times2.append(t2)
        assert times == times2, type(proc).__name__


def test_trace_validation():
    with pytest.raises(ValueError, match="rate"):
        ConstantRate(0.0)
    with pytest.raises(ValueError, match="amplitude"):
        DiurnalRate(1.0, amplitude=1.5)
    with pytest.raises(ValueError, match="non-stationary"):
        BurstyRate(1.0, jump=2.0, decay=1.0)
    with pytest.raises(ValueError, match="zero"):
        PiecewiseRate([0.0, 0.0])
    with pytest.raises(ValueError, match="buffer_policy"):
        TrafficConfig(buffer_policy="nope")
    with pytest.raises(ValueError, match="flush_interval"):
        TrafficConfig(buffer_policy="interval")
    with pytest.raises(ValueError, match="swap"):
        TrafficConfig(swap_to="fedavg")
    with pytest.raises(ValueError, match="trace"):
        TrafficConfig(trace="nope")


def test_sync_runtime_rejects_traffic(problem):
    with pytest.raises(ValueError, match="sync"):
        build_experiment("fedavg", runtime="sync",
                         traffic=TrafficConfig(), n_clients=8, rounds=1,
                         **problem)


# ------------------------------------------------- satellite: hourly traces


def test_hourly_mask_table_matches_synthetic_mask():
    """A (H, B) bucket table reproduces the synthetic-callable path the
    existing AvailabilitySampler tests use (ids % 2 == 0 online)."""
    pop = 64
    synthetic = AvailabilitySampler(lambda ids, t: ids % 2 == 0)
    empirical = AvailabilitySampler.from_hourly(np.array([[True, False]]))
    ids = np.arange(pop)
    for t in (0.0, 1.0, 7.5):
        np.testing.assert_array_equal(
            synthetic.available_fn(ids, t),
            empirical.available_fn(ids, t))
    # and the sampler machinery agrees end to end
    rng1, rng2 = (np.random.default_rng(9) for _ in range(2))
    c1 = synthetic.sample(rng1, pop, 8, t=0)
    c2 = empirical.sample(rng2, pop, 8, t=0)
    np.testing.assert_array_equal(np.sort(c1), np.sort(c2))


def test_hourly_fraction_table_is_deterministic_and_calibrated():
    fn = hourly_availability(np.array([0.25, 0.9]), hour_unit=2.0)
    ids = np.arange(20000)
    m0, m0b = fn(ids, 0.3), fn(ids, 1.9)       # same hour bin
    np.testing.assert_array_equal(m0, m0b)     # stable within the hour
    m1 = fn(ids, 2.1)                          # next bin
    assert abs(m0.mean() - 0.25) < 0.02
    assert abs(m1.mean() - 0.9) < 0.02
    m2 = fn(ids, 4.5)                          # table wraps: hour 0 again
    np.testing.assert_array_equal(m0, m2)


def test_hourly_trace_file_loading(tmp_path):
    table = np.array([[1.0, 0.0], [1.0, 1.0]])
    npy = tmp_path / "avail.npy"
    np.save(npy, table)
    csv = tmp_path / "avail.csv"
    np.savetxt(csv, np.array([0.5, 0.75]), delimiter=",")
    np.testing.assert_array_equal(load_hourly_trace(str(npy)), table)
    np.testing.assert_array_equal(load_hourly_trace(str(csv)),
                                  [0.5, 0.75])
    s = AvailabilitySampler.from_hourly(str(npy))
    ids = np.arange(10)
    np.testing.assert_array_equal(s.available_fn(ids, 0.0), ids % 2 == 0)
    np.testing.assert_array_equal(s.available_fn(ids, 1.0),
                                  np.ones(10, bool))
    with pytest.raises(ValueError, match="hour"):
        hourly_availability(np.zeros((0,)))
    with pytest.raises(ValueError, match="0, 1"):
        hourly_availability(np.array([2.0]))


# ------------------------------------------------------- churn and eviction


def test_membership_churn_deterministic():
    m = Membership(100, ChurnConfig(join_rate=1.0, leave_rate=1.0,
                                    initial_active=10, seed=4))
    assert m.n_active == 10
    st = m.state()
    seq = [(m.next_event(0.0), m.sample_join(), m.sample_leave())
           for _ in range(5)]
    m2 = Membership(100, ChurnConfig(join_rate=1.0, leave_rate=1.0,
                                     initial_active=10, seed=99))
    m2.load_state(st)
    seq2 = [(m2.next_event(0.0), m2.sample_join(), m2.sample_leave())
            for _ in range(5)]
    assert seq == seq2
    active = m.active_ids()
    assert all(m.is_active(c) for c in active)


def test_store_evict_client(tmp_path):
    from repro.core.algorithms import EF_STATE
    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    dense = DenseClientStore(EF_STATE, params, 6)
    dense.acquire([2])
    dense.state = jax.tree.map(lambda a: a.at[2].add(1.0), dense.state)
    assert dense.evict_client(2)
    assert not dense.evict_client(2)
    # the departed row is back to zero-init: a rejoin starts fresh
    assert all(float(jnp.abs(leaf[2]).sum()) == 0.0
               for leaf in jax.tree.leaves(dense.state))

    sparse = ClientStateStore(EF_STATE, params, population_size=10, budget=2,
                              spill_dir=str(tmp_path))
    sparse.acquire([0, 1])
    sparse.state = jax.tree.map(lambda a: a + 1.0, sparse.state)
    sparse.acquire([2])                    # spills the LRU (client 0)
    assert sparse.spills == 1
    assert sparse.evict_client(0)          # spilled: file unlinked
    assert not os.path.exists(sparse._spill_path(0))
    assert sparse.evict_client(1)          # resident: slot freed
    assert len(sparse._free) == 1
    assert not sparse.evict_client(7)      # never seen
    # evicted client re-acquires as fresh zero-init
    slot = int(sparse.acquire([0])[0])
    assert all(float(jnp.abs(leaf[slot]).sum()) == 0.0
               for leaf in jax.tree.leaves(sparse.state))


def test_churn_stream_traces_and_evicts(problem):
    kw = dict(problem, n_clients=8, rounds=2, seed=11)
    exp = build_experiment(
        "fedavg", async_cfg=AsyncConfig(**ACFG),
        traffic=TrafficConfig(
            trace="constant", trace_kwargs={"rate": 10.0},
            churn=ChurnConfig(join_rate=1.5, leave_rate=1.5,
                              initial_active=6, seed=2),
            eval_every=1.0), **kw)
    sink = MemorySink()
    attach(exp, sink)
    s = exp.run_stream(sim_budget=10.0)
    kinds = {e["event"] for e in sink.events}
    assert s["joins"] > 0 and s["leaves"] > 0
    assert "client_join" in kinds and "client_leave" in kinds
    assert "anytime_eval" in kinds
    leaves_inflight = [e for e in sink.events
                       if e["event"] == "client_leave" and e["in_flight"]]
    voided = [e for e in sink.events if e["event"] == "client_dropped"
              and e["reason"] == "client_left"]
    # every voided in-flight departure that completed inside the budget is
    # traced; some voided completions may still be pending past it
    assert len(voided) <= len(leaves_inflight)
    # anytime eval lands exactly on the simulated-time grid
    evals = [e for e in sink.events if e["event"] == "anytime_eval"]
    assert [e["sim_time"] for e in evals] == \
        [1.0 * (i + 1) for i in range(len(evals))]


# ---------------------------------------------------------------- hot-swap


def test_hotswap_mid_stream(problem):
    kw = dict(problem, n_clients=8, rounds=2, seed=11)
    tc = TrafficConfig(trace="constant", trace_kwargs={"rate": 8.0},
                       eval_every=1.0, swap_to="fedavg", swap_at=3.0)
    exp = build_experiment("fedpac_soap", async_cfg=AsyncConfig(**ACFG),
                           traffic=tc, **kw)
    sink = MemorySink()
    attach(exp, sink)
    exp.run_stream(sim_budget=7.0)
    assert exp.spec.name == "fedavg"
    swap_drops = [e for e in sink.events if e["event"] == "client_dropped"
                  and e["reason"] == "algo_swap"]
    assert swap_drops, "swap must discard in-flight/buffered work, traced"
    # the stream keeps flushing under the new algorithm
    assert any(r["round"] > 0 for r in exp.history)


def test_run_ab_shares_arrival_stream(problem):
    kw = dict(problem, n_clients=8, rounds=2, seed=11)
    tc = TrafficConfig(trace="diurnal",
                       trace_kwargs={"base": 6.0, "period": 4.0},
                       eval_every=1.0)
    a = build_experiment("fedavg", async_cfg=AsyncConfig(**ACFG),
                         traffic=tc, **kw)
    b = build_experiment("fedpac_soap", async_cfg=AsyncConfig(**ACFG),
                         traffic=tc, **kw)
    out = run_ab(a, b, sim_budget=5.0)
    # same seeds + same trace config -> identical arrival realizations:
    # the flush sim-times coincide even though the algorithms differ
    assert [r["sim_time"] for r in a.history] == \
        [r["sim_time"] for r in b.history]
    assert out["a"]["flushes"] == out["b"]["flushes"] > 0
    ttq = time_to_quality(out["eval_a"], "acc", 0.0)
    assert ttq == out["eval_a"][0]["sim_time"]
    assert time_to_quality(out["eval_a"], "acc", 2.0) is None


# ---------------------- satellite: mid-stream checkpoint, fresh process

_CKPT_SCRIPT = textwrap.dedent("""
    import json, sys
    import numpy as np
    import jax, jax.numpy as jnp
    sys.path.insert(0, {src!r})
    sys.path.insert(0, {testdir!r})
    from test_traffic import _mlp_problem, ACFG
    from repro.api import AsyncConfig, TrafficConfig, build_experiment
    from repro.obs import JsonlSink, attach

    mode, ckdir, tracefile = sys.argv[1], sys.argv[2], sys.argv[3]
    kw = dict(_mlp_problem(), n_clients=8, rounds=2, seed=11)
    tc = TrafficConfig(trace="constant", trace_kwargs={{"rate": 8.0}},
                       eval_every=1.0)
    exp = build_experiment("fedpac_soap", async_cfg=AsyncConfig(**ACFG),
                           traffic=tc, **kw)
    attach(exp, JsonlSink(tracefile))
    if mode == "full":
        exp.run_stream(sim_budget=3.0)
        exp.save_checkpoint(ckdir)
        seq0 = exp.tracer.seq
    else:
        exp.load_checkpoint(ckdir)
        seq0 = exp.tracer.seq
    exp.run_stream(sim_budget=7.0)
    print("RESULT " + json.dumps({{
        "seq0": seq0,
        "history": exp.history,
        "eval": exp.eval_history,
        "sim_now": exp.sim_now,
    }}))
""")


def _run_ckpt(mode, ckdir, tracefile):
    script = _CKPT_SCRIPT.format(src=os.path.abspath(SRC),
                                 testdir=os.path.dirname(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", script,
                           mode, ckdir, tracefile],
                          capture_output=True, text=True, timeout=900,
                          env=env)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def _events(path, seq0):
    """Trace events from seq0 on, wall-clock durations stripped."""
    out = []
    with open(path) as f:
        for line in f:
            ev = json.loads(line)
            if ev["seq"] < seq0:
                continue
            ev.pop("dur_s", None)
            out.append(ev)
    return out


def test_midstream_checkpoint_rollback_fresh_process(tmp_path):
    """Stop at sim time t, restore in a fresh process, replay: trailing
    trace events and final metrics identical to the uninterrupted run."""
    ckdir = str(tmp_path / "ck")
    full = _run_ckpt("full", ckdir, str(tmp_path / "full.jsonl"))
    resumed = _run_ckpt("resume", ckdir, str(tmp_path / "resume.jsonl"))
    assert resumed["seq0"] == full["seq0"]
    assert resumed["history"] == full["history"]
    assert resumed["eval"] == full["eval"]
    assert resumed["sim_now"] == full["sim_now"]
    assert _events(str(tmp_path / "resume.jsonl"), resumed["seq0"]) == \
        _events(str(tmp_path / "full.jsonl"), full["seq0"])


# ------------------- satellite: sharded executor on a multi-device mesh

_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {src!r})
    import numpy as np
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.core.engine.executors import ExecutorConfig, \\
        make_cohort_executor

    assert len(jax.devices()) == 4, jax.devices()
    mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))

    def one_client(batch):
        return {{"out": batch * 2.0, "s": jnp.tanh(batch @ batch.T).sum()}}

    rng = np.random.default_rng(0)
    batches = jnp.asarray(rng.normal(size=(8, 5, 5)).astype(np.float32))
    ref = make_cohort_executor(ExecutorConfig("vmap"))(one_client, batches)
    for backend in ("shard_map", "sharded"):
        got = make_cohort_executor(ExecutorConfig(
            backend, chunk_size=1, mesh=mesh))(one_client, batches)
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)
    print("SHARDED-4DEV-OK")
""")


def test_sharded_executor_on_forced_multidevice_mesh():
    """The population-scale 'sharded' executor on a real 4-device mesh
    (subprocess: jax pins the device count at first init)."""
    script = _SHARDED_SCRIPT.format(src=os.path.abspath(SRC))
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=900,
                          env=dict(os.environ))
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SHARDED-4DEV-OK" in proc.stdout
