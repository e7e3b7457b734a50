"""Device time by the round program's named scopes, idle time by the
program's own host spans, and the five metrics that read them."""
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from chipbench import scopes as SC  # noqa: E402
from chipbench import spec as S  # noqa: E402
from chipbench import xtrace as X  # noqa: E402

MS = 1e6  # nanoseconds
READERS = ("local_model_ms", "local_precond_ms", "precond_refresh_ms",
           "server_ms", "staging_idle_ms")
ROUND = "jit(round_fn)/vmap(one_client)/while/body/closed_call"


def op(name, opcode, start_ms, dur_ms, path="", program="7"):
    return SC.Op(X.Event(name, start_ms * MS, dur_ms * MS), path, opcode,
                 program)


def span(phase, start_ms, dur_ms):
    return X.Event(SC.SPAN_PREFIX + phase, start_ms * MS, dur_ms * MS)


def scoped_round():
    """One round in a 100 ms window: a local-step loop (``while``) holds a
    model op, an optimizer op and a refresh ``conditional`` whose body is a
    refresh op nested in the optimizer's scope; a flush, a server op, an
    op of no scope and an eval op of another program follow."""
    return {"/device:TPU:0": [
        op("while.7", "while", 10, 50, ROUND[:13]),
        op("fusion.1", "fusion", 10, 20, ROUND + "/transpose(jvp("
           "local_model))/dot_general"),
        op("fusion.2", "fusion", 30, 10, ROUND + "/local_precond/mul"),
        op("cond.3", "conditional", 40, 15, ROUND + "/local_precond/cond"),
        op("custom-call.4", "custom-call", 40, 15, ROUND + "/local_precond"
           "/cond/branch_1_fun/precond_refresh/jit(qr)/geqrf"),
        op("copy.5", "copy", 55, 5),                 # no metadata
        op("fusion.6", "fusion", 60, 6, "jit(round_fn)/flush/dot_general"),
        op("fusion.7", "fusion", 66, 2, "jit(round_fn)/server_update/add"),
        op("fusion.8", "fusion", 68, 4, "jit(eval_fn)/dot_general",
           program="9"),
    ]}


def test_device_time_goes_to_the_innermost_scope_of_leaf_ops():
    att = SC.attribute(scoped_round(), [], (0, 100 * MS))
    got = {k: round(v * 1e3, 9) for k, v in att.device_by_scope.items()}
    # the while loop and the conditional span their bodies: left out
    assert got == {"local_model": 20.0, "local_precond": 10.0,
                   "precond_refresh": 15.0, "flush": 6.0,
                   "server_update": 2.0, "unscoped": 9.0}
    assert att.scoped and att.n_devices == 1
    # of the unscoped ops, only the copy belongs to the scoped program
    assert att.unscoped_in_program == pytest.approx(0.005)
    assert [row[0] for row in att.unscoped_top] == ["copy.5"]


def test_a_scope_counts_the_union_of_its_ops_inside_the_window():
    ops = {"/device:TPU:0": [
        op("fusion.1", "fusion", -5, 10, "local_model/a"),   # clipped
        op("fusion.2", "fusion", 2, 6, "local_model/b"),     # overlaps
        op("fusion.3", "fusion", 95, 10, "flush/c")]}        # clipped
    att = SC.attribute(ops, [], (0, 100 * MS))
    assert att.device_by_scope["local_model"] == pytest.approx(0.008)
    assert att.device_by_scope["flush"] == pytest.approx(0.005)


def test_idle_time_goes_to_the_innermost_program_span():
    spans = [span("staging", 0, 10), span("stage_batches", 2, 5),
             span("update", 10, 60), span("readback", 70, 2),
             span("eval", 72, 20)]
    att = SC.attribute(scoped_round(), spans, (0, 100 * MS))
    got = {k: round(v * 1e3, 9) for k, v in att.idle_by_span.items()}
    # gaps 0..10 (midpoint 5: stage_batches), 72..100 (midpoint 86: eval)
    assert got == {"stage_batches": 10.0, "eval": 28.0}
    spans[1] = span("stage_batches", 6, 2)       # 5 is in staging alone
    att = SC.attribute(scoped_round(), spans, (0, 100 * MS))
    assert set(att.idle_by_span) == {"staging", "eval"}
    att = SC.attribute(scoped_round(), [], (0, 100 * MS))
    assert att.idle_by_span == {"outside": pytest.approx(0.038)}


def test_idle_by_span_splits_the_gaps_that_reduce_finds():
    trace = X.Trace({d: [o.event for o in ops]
                     for d, ops in scoped_round().items()},
                    [X.Event("bench.window", 0, 100 * MS)])
    r = X.reduce(trace)
    att = SC.attribute(scoped_round(), [span("update", 0, 100)],
                       X.window_of(trace))
    assert att.window_s == r.window_s and att.n_devices == r.n_devices
    assert att.idle_by_span["update"] == pytest.approx(
        sum(s for _, s in r.idle_gaps))


@pytest.mark.parametrize("text, want", [
    ("%fusion.44 = f32[256,256]{1,0:T(8,128)} fusion(f32[256,256]{1,0:"
     "T(8,128)} %x.1), kind=kOutput", "fusion"),
    ("%while.4 = (s32[]{:T(128)}, bf16[256]{0:T(128)(2,1)S(1)}) while("
     "(s32[]{:T(128)}) %t), condition=%c, body=%b", "while"),
    ("%cond.0.clone.9 = (f32[256,256]{1,0:T(8,128)}) conditional(s32[]{:T"
     "(128)} %p, f32[2]{0} %a)", "conditional"),
    ("%custom-call.12 = (f32[256,128]{1,0:T(8,128)S(1)}, f32[128]{0:T(128)"
     "S(1)}) custom-call(f32[256,128]{1,0:T(8,128)S(1)} %s)", "custom-call"),
    ("%copy-start = (f32[8]{0:S(1)}, u32[]{:S(2)}) copy-start(f32[8]{0} %x)",
     "copy-start"),
])
def test_the_opcode_of_an_op(text, want):
    assert SC.opcode(text) == want


@pytest.mark.parametrize("path, want", [
    (ROUND + "/local_precond/cond/branch_1_fun/precond_refresh/jit(qr)/"
     "geqrf", "precond_refresh"),
    (ROUND + "/transpose(jvp(local_model))/dot_general", "local_model"),
    ("jit(round_fn)/flush/vmap(jit(dequant_accumulate))/pallas_call",
     "flush"),
    ("jit(eval_fn)/dot_general", None),
    ("", None),
    ("jit(round_fn)/local_models_extra/add", None),
])
def test_the_innermost_scope_of_a_path(path, want):
    assert SC.scope_of(path) == want


def test_the_vocabulary_is_the_programs():
    from repro.obs import SCOPES
    assert SC.SCOPES == SCOPES


def _xspace(path, device_events, host_spans, tf_op=True):
    """A TPU-like trace file: ``device_events`` are (hlo text, scope path,
    start ms, duration ms); the op's path rides in its event metadata as
    the ``tf_op`` stat, as a TPU trace has it."""
    from jax.profiler import ProfileData
    dev_md, dev_ev = [], []
    for i, (text, scope, start, dur) in enumerate(device_events, 1):
        stats = (f'stats {{ metadata_id: 1 str_value: "{scope}:op" }} '
                 if scope and tf_op else "")
        stats += "stats { metadata_id: 2 uint64_value: 11549737827119076949 }"
        dev_md.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                      f'"{text}" {stats} }} }}')
        dev_ev.append(f"events {{ metadata_id: {i} offset_ps: "
                      f"{int(start * 1e9)} duration_ps: {int(dur * 1e9)} }}")
    host_md, host_ev = [], []
    for i, (name, start, dur) in enumerate(host_spans, 1):
        host_md.append(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                       f'"{name}" }} }}')
        host_ev.append(f"events {{ metadata_id: {i} offset_ps: "
                       f"{int(start * 1e9)} duration_ps: {int(dur * 1e9)} }}")
    text = (
        'planes { id: 1 name: "/device:TPU:0" lines { id: 1 name: "XLA Ops" '
        f'timestamp_ns: 0 {" ".join(dev_ev)} }} {" ".join(dev_md)} '
        'stat_metadata { key: 1 value { id: 1 name: "tf_op" } } '
        'stat_metadata { key: 2 value { id: 2 name: "program_id" } } } '
        'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" '
        f'timestamp_ns: 0 {" ".join(host_ev)} }} {" ".join(host_md)} }}')
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))
    return str(path)


DEVICE = [
    ("%while.1 = (f32[2]{0}) while(f32[2]{0} %p)", "", 10, 60),
    ("%fusion.2 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
     ROUND + "/jvp(local_model)/dot", 10, 20),
    ("%fusion.3 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
     ROUND + "/local_precond/mul", 30, 12),
    ("%custom-call.4 = f32[2]{0} custom-call(f32[2]{0} %p)",
     ROUND + "/local_precond/cond/branch_1_fun/precond_refresh/qr", 42, 8),
    ("%fusion.5 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
     "jit(round_fn)/flush/add", 50, 6),
    ("%fusion.6 = f32[2]{0} fusion(f32[2]{0} %p), kind=kLoop",
     "jit(round_fn)/server_update/add", 56, 4),
]
HOST = [("bench.window", 0, 100), ("repro.staging", 0, 10),
        ("repro.stage_batches", 1, 6), ("repro.update", 10, 60),
        ("repro.eval", 70, 20)]


def _ctx(path, rounds=2):
    trace = X.load(path)
    return types.SimpleNamespace(reduced=X.reduce(trace), rounds=rounds)


def _read(ctx):
    cell = S.load_cell("vit_s16.c8_k10_qblock")
    read = {m.name: m.read for m in cell.per_layer}
    return {name: read[name](ctx) for name in READERS}


def test_the_five_readers_read_a_scoped_trace_file(tmp_path):
    got = _read(_ctx(_xspace(tmp_path / "t.xplane.pb", DEVICE, HOST)))
    # ms a round over 2 rounds; idle 0..10 (midpoint 5: stage_batches) and
    # 70..100 (midpoint 85: eval), of which staging holds the first
    assert got == pytest.approx({
        "local_model_ms": 10.0, "local_precond_ms": 6.0,
        "precond_refresh_ms": 4.0, "server_ms": 5.0,
        "staging_idle_ms": 5.0})


@pytest.mark.parametrize("reader", READERS)
def test_a_reader_reads_nothing_without_scopes_or_spans(tmp_path, reader):
    bare = _xspace(tmp_path / "t.xplane.pb", DEVICE, HOST[:1], tf_op=False)
    assert _read(_ctx(bare))[reader] is None
    # a parent program: paths without the vocabulary, no program spans
    unnamed = [(t, p.replace("local_", "x_").replace("precond_refresh", "r")
                .replace("flush", "f").replace("server_update", "s"), s, d)
               for t, p, s, d in DEVICE]
    parent = _xspace(tmp_path / "p.xplane.pb", unnamed, HOST[:1])
    assert _read(_ctx(parent))[reader] is None
    # a reduction of another trace than the last one loaded
    other = types.SimpleNamespace(
        reduced=X.reduce(X.Trace({"/device:TPU:0": [X.Event("f.1", 0, 1)]},
                                 [X.Event("bench.window", 0, 5 * MS)])),
        rounds=2)
    assert _read(other)[reader] is None


def test_load_still_returns_what_xtrace_reads(tmp_path):
    path = _xspace(tmp_path / "t.xplane.pb", DEVICE, HOST)
    assert X.load.keeps_scopes
    assert X.load(path) == X.load.__wrapped__(path)


def test_metadata_that_do_not_line_up_leave_ops_unscoped(tmp_path):
    path = _xspace(tmp_path / "t.xplane.pb", DEVICE, HOST)
    trace = X.load(path)
    md = SC.device_op_metadata(path)
    dev = "/device:TPU:0"
    assert [SC.scope_of(p) for _, p, _ in md[dev]] == [
        None, "local_model", "local_precond", "precond_refresh", "flush",
        "server_update"]
    md[dev] = md[dev][1:]
    assert all(o.path == "" for o in SC.scoped_ops(trace, md)[dev])
