"""One run of one cell: build, warm up and check, measure, compare.

A cell runs the algorithm its mix names (``algorithm``: any registered one,
``fedpac_soap``, ``fedpac_muon``, ``fedavg``, ...), its local optimizer set
from the mix's block named after that optimizer (``soap``, ``muon``,
``sgd``, ...).  The run builds the experiment through the system's normal
path (``repro.api.build_experiment`` on a registered scenario source), then:

  set-up   the first ``check_rounds`` rounds go through the window's own call
           (``FederatedExperiment.run_round``) on the window's own feed; they
           compile every program the window runs and are recorded for the
           comparison: each round's loss, the global direction g_G and
           Theta after round 1, and the parameters' change after the last;
  window   back-to-back rounds for ``seconds``; a round ends when the new
           server parameters are ready;
  check    after the window, with the program's state freed, the plain
           reference follows the recorded rounds on the same batches from
           the same seed-made weights, and each number is held to its limit.

Host phases are wrapped in ``jax.profiler.TraceAnnotation`` spans named
``bench.<phase>`` so that a traced run can say what the host was doing while
the device was idle.
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import statistics
import sys
import tempfile
import time
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops as F
from chipbench import peaks as P
from chipbench import spec as S
from chipbench import xtrace as X
from chipbench.faults import FAULTS

GIB = 2.0 ** 30
# leaves whose first gradient is under this share of the median leaf's move
# by round-off alone and are left out of the gradient and change numbers
NOUGHT_GRAD = 1e-3
# every number ``compare`` computes; a cell's limits file names those that
# decide its ``correct``
NUMBERS = ("loss", "loss_r1", "grad", "theta", "delta", "grad_median",
           "theta_median", "delta_median")


class CompileEvents:
    """Counts what JAX compiles and what its persistent cache serves."""

    def __init__(self):
        self.hits = self.misses = self.compiles = 0
        self.compile_s = self.trace_s = 0.0

    def install(self) -> "CompileEvents":
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        return self

    def _event(self, name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif name == "/jax/core/compile/jaxpr_trace_duration":
            self.trace_s += secs


def enable_cache(path: str) -> str:
    """JAX's persistent compilation cache at a fixed path; the program's own
    helper (``repro.utils.hw.enable_compile_cache``) reads the same
    variable, so both use one directory."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no size cap: a capped cache refuses the largest programs, which then
    # compile again in every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


def load_family(cell: S.Cell, bench_dir: str = S.BENCH_DIR):
    fam = cell.config["family"]
    ref = S.load_module(os.path.join(bench_dir, "references", fam + ".py"),
                        "reference")
    binding = S.load_module(os.path.join(bench_dir, "bindings", fam + ".py"),
                            "binding")
    algo = S.load_module(os.path.join(bench_dir, "references",
                                      cell.traffic["algorithm"] + ".py"),
                         "reference")
    return ref, binding, algo


def set_precision(name: str) -> None:
    """The matrix-product precision the configuration states, for every
    program this process traces from now on (the reference sets its own)."""
    jax.config.update("jax_default_matmul_precision", name)


def optimizer_kwargs(traffic: dict) -> dict:
    """The local optimizer's settings: the mix's block named after the
    optimizer its algorithm runs (``soap``, ``muon``, ``sgd``, ...), whole."""
    from repro.api import resolve
    name = resolve(traffic["algorithm"]).optimizer
    if name not in traffic:
        raise KeyError(f"the mix of algorithm {traffic['algorithm']!r} has "
                       f"no {name!r} block, the settings of its optimizer")
    return dict(traffic[name])


def build(cell: S.Cell, seed: int, ref, binding, precision=None):
    """The experiment, through the system's normal path: the algorithm the
    mix names, its optimizer set from the mix's block of that name,
    parameters in the dtype the configuration states, products at its
    precision unless ``precision`` names another (a control run), data from
    the mix's fixed data seed, weights and every sampling draw from
    ``seed``."""
    from repro.api import build_experiment
    set_precision(precision or cell.config["matmul_precision"])
    t = cell.traffic
    opt = optimizer_kwargs(t)
    dtype = jnp.dtype(cell.config[binding.DTYPE_KEY])
    spec = binding.scenario(cell.config, t, ref, dtype, seed)
    return build_experiment(
        t["algorithm"], scenario=spec, scenario_seed=t["data"]["seed"],
        opt_kwargs=opt, seed=seed, n_clients=t["n_clients"],
        participation=t["participation"], local_steps=t["local_steps"],
        batch_size=t["batch_size"], lr=t["lr"], beta=t["beta"],
        server_lr=t["server_lr"], executor=t["executor"],
        chunk_size=t["chunk_size"], theta_codec=t["theta_codec"],
        delta_codec=t["delta_codec"], qblock_size=t["qblock_size"])


class Probe:
    """Wraps one experiment's staging, round program and eval in host spans,
    and records the staged batches while ``capture`` is a list."""

    def __init__(self, exp):
        self.capture = None
        stage, round_fn, eval_fn = exp._stage_batches, exp.round_fn, \
            exp.eval_fn

        def staged(cohort):
            with jax.profiler.TraceAnnotation("bench.staging"):
                batches = stage(cohort)
            if self.capture is not None:
                self.capture.append(jax.device_get(batches))
            return batches

        def rounded(*args):
            with jax.profiler.TraceAnnotation("bench.round_fn"):
                return round_fn(*args)

        def evaluated(params):
            with jax.profiler.TraceAnnotation("bench.eval"):
                return eval_fn(params)

        exp._stage_batches, exp.round_fn = staged, rounded
        if eval_fn is not None:
            exp.eval_fn = evaluated


def timed_round(exp) -> dict:
    with jax.profiler.TraceAnnotation("bench.round"):
        rec = exp.run_round()
        with jax.profiler.TraceAnnotation("bench.sync"):
            jax.block_until_ready(exp.server.params)
    return rec


@jax.jit
def _norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                        - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def named_norms(algo, tree, norms, strip: int = 0) -> dict:
    names = [algo.path_name(p[strip:]) for p, _ in algo.leaf_paths(tree)]
    return dict(zip(names, map(float, jax.device_get(norms))))


def check_rounds(exp, probe: Probe, algo, n: int):
    """The first ``n`` rounds, recorded; returns the program's readings and
    the staged batches of each round (host arrays)."""
    p0 = exp.server.params
    captured, prog = [], {"loss": []}
    probe.capture = captured
    for r in range(n):
        rec = timed_round(exp)
        prog["loss"].append(rec["loss"])
        if r == 0:
            prog["grad"] = named_norms(algo, exp.server.g_global,
                                       _norms(exp.server.g_global))
            theta = exp.server.theta
            prog["theta"] = ({} if theta is None else
                             named_norms(algo, theta, _norms(theta), strip=1))
            prog["theta_sizes"] = [int(np.prod(x.shape))
                                   for x in jax.tree.leaves(theta)]
    prog["delta"] = named_norms(algo, p0, _diff_norms(exp.server.params, p0))
    probe.capture = None
    return prog, captured


def run_window(exp, seconds: float, counter: CompileEvents):
    compiles0 = counter.compiles
    times, losses = [], []
    with jax.profiler.TraceAnnotation("bench.window"):
        t_start = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            rec = timed_round(exp)
            t1 = time.perf_counter()
            times.append(t1 - t0)
            losses.append(rec["loss"])
            if t1 - t_start >= seconds:
                break
    return {"window_s": t1 - t_start, "times": times, "losses": losses,
            "compiles": counter.compiles - compiles0}


def reference_readings(cell: S.Cell, ref, algo, seed: int,
                       rounds) -> dict:
    cfg = cell.config
    params0 = ref.init_params(cfg, seed)
    return algo.run(params0, lambda p, b: ref.loss(p, b, cfg),
                    ref.preconditioned, rounds, cell.traffic)


def _leaf_gaps(prog: dict, ref: dict, exclude: set) -> dict:
    """|prog - ref| of each leaf's norm, over the reference's norm of that
    leaf or of the median leaf, whichever is larger."""
    names = [n for n in ref if n not in exclude]
    if not names:
        return {}
    if set(prog) != set(ref):
        return {"leaf sets differ": math.inf}
    med = statistics.median(ref[n] for n in names)
    gaps = {}
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        gaps[n] = gap if math.isfinite(gap) else math.inf
    return gaps


def compare(prog: dict, ref: dict) -> dict:
    """Each number ``NUMBERS`` names, as (value, where it is set)."""
    fg = ref["first_grad"]
    med = statistics.median(fg.values())
    exclude = {n for n, g in fg.items() if g < NOUGHT_GRAD * med}
    loss_gaps = [abs(p - r) / abs(r) if math.isfinite(p) else math.inf
                 for p, r in zip(prog["loss"], ref["loss"])]
    worst_round = int(np.argmax(loss_gaps))
    out = {"loss": (loss_gaps[worst_round], f"round {worst_round + 1}"),
           "loss_r1": (loss_gaps[0], "round 1")}
    for key in ("grad", "theta", "delta"):
        gaps = _leaf_gaps(prog[key], ref[key],
                          exclude if key != "theta" else set())
        if not gaps:
            out[key] = out[key + "_median"] = (0.0, None)
            continue
        where = max(gaps, key=gaps.get)
        out[key] = (gaps[where], where)
        out[key + "_median"] = (statistics.median(gaps.values()), "median")
    out["excluded"] = sorted(exclude)
    return out


def judge(gaps: dict, limits: dict, failed: int) -> tuple:
    """``correct`` and the checks: each number the limits name, beside its
    limit."""
    checks = {k: {"value": gaps[k][0], "limit": lim}
              for k, lim in limits.items()}
    ok = failed == 0 and all(c["value"] <= c["limit"]
                             for c in checks.values())
    return ok, checks


def device_info(chips: int) -> dict:
    devs = jax.devices()[:chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def per_layer(cell: S.Cell, ctx) -> dict:
    out = {}
    for m in cell.per_layer:
        v = m.read(ctx)
        if v is not None:
            out[m.name] = {"value": float(v), "unit": m.unit}
    return out


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def run_cell(cell: S.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, counter: CompileEvents, precision=None,
             fault=None) -> dict:
    """One run; returns the result line.  ``precision`` runs the program's
    products at another precision than the configuration states, and
    ``fault`` names a fault (``faults.FAULTS``) planted under the timed
    path: both make control runs, which have to come out not correct."""
    ref, binding, algo = load_family(cell)
    chips = cell.chips
    exp = build(cell, seed, ref, binding, precision=precision)
    if fault is not None:
        FAULTS[fault](exp)
    probe = Probe(exp)
    tracer_sink = trace_dir = None
    if trace:
        # the system's tracer is on from the first round, so that what it
        # compiles to report its spans is compiled in set-up
        from repro.obs import MemorySink, Tracer
        tracer_sink = MemorySink()
        exp.tracer = Tracer(sinks=(tracer_sink,))
    n_check = int(cell.traffic["check_rounds"])
    prog, captured = check_rounds(exp, probe, algo, n_check)
    setup_s = time.perf_counter() - t_start
    setup_compiles = counter.compiles

    if trace:
        tracer_sink.events.clear()
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        jax.profiler.start_trace(trace_dir)
    win = run_window(exp, seconds, counter)
    if trace:
        jax.profiler.stop_trace()
    device = device_info(chips)
    del exp, probe          # the program's state, before the reference runs
    gc.collect()
    jax.clear_caches()

    times = win["times"]
    failed = sum(1 for x in win["losses"] + prog["loss"]
                 if not math.isfinite(x))
    print(f"compile: cache_hits={counter.hits} cache_misses="
          f"{counter.misses} compiles_in_setup={setup_compiles} "
          f"compile_s={counter.compile_s!r} trace_s={counter.trace_s!r} "
          f"compiles_in_window={win['compiles']}")
    q = statistics.quantiles(times, n=10) if len(times) > 1 else times * 9
    print(f"rounds: n={len(times)} window_s={win['window_s']!r} "
          f"p50_s={statistics.median(times)!r} p90_s={q[-1]!r} "
          f"setup_s={setup_s!r} peak_bytes={device['memory_peak_bytes']}")

    result = {"correct": False, "attempted": len(times), "failed": failed}
    if trace:
        reduced = X.reduce(X.load(X.find_xplane(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = types.SimpleNamespace(
            reduced=reduced, spans=tracer_sink.events, rounds=len(times),
            peaks=P.for_kind(device["kind"]),
            round_flops=F.round_model_flops(ref, cell.config, cell.traffic),
            theta_sizes=prog["theta_sizes"],
            clients=F.clients_per_round(cell.traffic),
            traffic=cell.traffic, config=cell.config)
        result["metrics"] = per_layer(cell, ctx)
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
        result["breakdown"] = {
            "device_ops": X.top(reduced.op_seconds.items()),
            "idle_gaps": [[n, s] for n, s in reduced.idle_gaps[:10]]}
    else:
        metrics = {"round_s": win["window_s"] / len(times),
                   "peak_hbm_gib": device["memory_peak_bytes"] / GIB,
                   "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": metrics[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = device

    t_ref = time.perf_counter()
    ref_out = reference_readings(cell, ref, algo, seed, captured)
    gaps = compare(prog, ref_out)
    ok, checks = judge(gaps, cell.limits, failed)
    result["correct"] = ok
    result["checks"] = checks
    print(f"reference: rounds={n_check} seconds="
          f"{time.perf_counter() - t_ref!r} excluded={gaps['excluded']}")
    log("readings: " + " ".join(f"{k}={gaps[k][0]!r}" for k in NUMBERS))
    for k, lim in cell.limits.items():
        log(f"check {k}: {gaps[k][0]!r} limit {lim!r} ({gaps[k][1]}) "
            f"{'ok' if gaps[k][0] <= lim else 'FAIL'}")
    log(f"correct: {ok} failed_rounds={failed}")
    return result
