"""Cells, configurations, mixes and metrics load by name from files."""
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import chipbench_cells as C  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench import spec as S  # noqa: E402
from chipbench import xtrace as X  # noqa: E402


def test_the_committed_cells_load():
    bench = S.load_benchmark()
    for w in bench["workloads"]:
        cell = S.load_cell(w["name"])
        assert cell.config["family"] in ("vit", "llama")
        assert cell.limits and set(cell.limits) <= set(harness.NUMBERS)
        names = {m.name for m in cell.per_layer}
        assert {"staging_ms", "eval_ms", "device_idle_pct",
                "round_mfu"} <= names
        assert {m["name"] for m in cell.end_to_end} == {
            "round_s", "peak_hbm_gib", "setup_s"}


def test_kernel_metrics_report_only_where_listed():
    vit = {m.name for m in S.load_cell("vit_s16.c8_k10_qblock").per_layer}
    lm = {m.name for m in S.load_cell("smollm360m.c4_seq1024").per_layer}
    assert {"qblock_roofline", "fused_agg_roofline"} <= vit
    assert not {"qblock_roofline", "fused_agg_roofline"} & lm


def test_a_new_cell_loads_from_added_files_alone(tmp_path):
    root = C.make_tree(str(tmp_path))
    bench_dir = os.path.join(root, "bench")
    # a new configuration, mix, limits and metric: files only
    cfg = dict(C.TINY_VIT, hidden_size=48)
    for kind, name, obj in (("configs", "new-vit", cfg),
                            ("traffic", "new_mix",
                             dict(C.TINY_VIT_MIX, local_steps=3)),
                            ("limits", "new_vit.mix", {"limits": C.LIMITS})):
        with open(os.path.join(bench_dir, kind, name + ".json"), "w") as f:
            json.dump(obj, f)
    with open(os.path.join(bench_dir, "metrics", "rounds_seen.py"),
              "w") as f:
        f.write("def read(ctx):\n    return ctx.rounds\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "new_vit.mix", "config": "new-vit",
                               "traffic": "new_mix", "chips": 1,
                               "why": "test"})
    bench["per_layer"].append({"name": "rounds_seen", "unit": "rounds",
                               "better": "higher", "source": "host_clock",
                               "layer": "test", "moves": "round_s",
                               "workloads": ["new_vit.mix"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = S.load_cell("new_vit.mix", repo_root=root)
    assert cell.config["hidden_size"] == 48
    assert cell.traffic["local_steps"] == 3
    metric = {m.name: m for m in cell.per_layer}["rounds_seen"]
    assert metric.read(types.SimpleNamespace(rounds=7)) == 7
    # the new metric stays out of the cells it does not list
    other = S.load_cell("tiny_lm.d", repo_root=root)
    assert "rounds_seen" not in {m.name for m in other.per_layer}


def test_unknown_names_are_refused(tmp_path):
    root = C.make_tree(str(tmp_path))
    with pytest.raises(S.UnknownNameError, match="no_such_cell"):
        S.load_cell("no_such_cell", repo_root=root)
    os.remove(os.path.join(root, "bench", "traffic", "tiny_q.json"))
    with pytest.raises(S.UnknownNameError, match="traffic"):
        S.load_cell("tiny_vit.q", repo_root=root)


def _ctx(ops=(), spans=(), rounds=2, window_ms=100.0, theta_sizes=(1000,)):
    trace = X.Trace({"/device:TPU:0": list(ops)} if ops else {},
                    [X.Event("bench.window", 0, window_ms * 1e6)])
    from chipbench import peaks as P
    return types.SimpleNamespace(
        reduced=X.reduce(trace), spans=list(spans), rounds=rounds,
        peaks=P.for_kind("TPU v5 lite"), round_flops=1e12,
        theta_sizes=list(theta_sizes), clients=8,
        traffic={"theta_codec": "qblock", "qblock_size": 128}, config={})


def _readers():
    cell = S.load_cell("vit_s16.c8_k10_qblock")
    return {m.name: m.read for m in cell.per_layer}


def test_readers_return_nothing_when_there_is_nothing_to_read():
    got = {name: read(_ctx()) for name, read in _readers().items()}
    assert all(v is None for v in got.values()), got


def test_readers_read_spans_trace_and_shapes():
    read = _readers()
    spans = [{"event": "span", "phase": "staging", "dur_s": 0.010},
             {"event": "span", "phase": "staging", "dur_s": 0.030},
             {"event": "span", "phase": "eval", "dur_s": 0.004}]
    # one qblock call per leaf per round, 10 us each; busy 40 of 100 ms
    ops = [X.Event("fusion.1", 0, 40e6),
           X.Event("vmap_jit_quantize__.3", 50e6, 10e3, kernel=True),
           X.Event("vmap_jit_quantize__.3", 60e6, 10e3, kernel=True)]
    ctx = _ctx(ops=ops, spans=spans)
    assert read["staging_ms"](ctx) == pytest.approx(20.0)
    assert read["eval_ms"](ctx) == pytest.approx(4.0)
    assert read["device_idle_pct"](ctx) == pytest.approx(
        100 * (1 - 0.04002 / 0.1))
    # 2 rounds x 1e12 FLOP in 0.1 s over 197 TFLOP/s
    assert read["round_mfu"](ctx) == pytest.approx(100 * 2e12 / 0.1 / 197e12)
    least = 8 * (5000 + 32) / 819e9          # memory-bound, per call
    assert read["qblock_roofline"](ctx) == pytest.approx(
        100 * 2 * least / 20e-6)
    assert read["fused_agg_roofline"](ctx) is None   # no such events
    # an op named like the kernel that is no Pallas kernel does not count
    plain = [X.Event("quantize.4", 50e6, 10e3)]
    assert read["qblock_roofline"](_ctx(ops=plain)) is None
    # events that do not map onto the Theta leaves give no share
    assert read["qblock_roofline"](_ctx(ops=ops, theta_sizes=(10, 20, 30))) \
        is None


NAME = r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}"
UNIT = r"[A-Za-z0-9_/%.-]{1,16}"


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_the_benchmark_file_keeps_its_shape():
    """BENCHMARK.json: the keys, names, units and bounds it may hold."""
    import re
    bench = S.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert all(_one_line(w) for w in bench["command"])
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.fullmatch(NAME, c["name"]) and _one_line(c["why"])
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(S.REPO_ROOT, c["file"])) as f:
            assert json.load(f)["reduced"] == c["reduced"]
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.fullmatch(NAME, w["name"]) and _one_line(w["why"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        pairs.add((w["config"], w["traffic"]))
    assert len(pairs) == len(bench["workloads"])
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert re.fullmatch(NAME, m["name"]) and re.fullmatch(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and _one_line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [x["name"] for x in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
