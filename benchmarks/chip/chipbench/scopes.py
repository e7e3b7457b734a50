"""Attribute a traced window's device time to the round program's named
scopes, and its idle time to the program's own host spans.

The program names the parts of its round program with ``jax.named_scope``
(``repro.obs.SCOPES``; ``SCOPES`` below is the benchmark's copy) and, with
its tracer on, opens a ``jax.profiler.TraceAnnotation`` named
``repro.<phase>`` around each host phase.  A TPU trace keeps each op's
scope path as the ``tf_op`` stat of the op's event metadata
(``jit(round_fn)/while/body/.../local_precond/precond_refresh/.../geqrf:``)
and the program it belongs to as ``program_id``; ``jax.profiler.ProfileData``
does not expose event metadata, so they are read from the trace's XSpace
protobuf.

  device_by_scope  seconds a chip spent in each scope's leaf ops: the union
                   of their intervals inside the window.  A leaf op goes to
                   the innermost name of ``SCOPES`` on its path, else to
                   ``unscoped``.  ``while``, ``conditional`` and ``call``
                   ops span their bodies, whose ops the trace also lists,
                   and are left out.
  idle_by_span     seconds a chip was idle (the gaps ``xtrace.reduce``
                   finds), by the innermost ``repro.*`` span open on the
                   host at each gap's midpoint, else ``outside``.

The harness hands the metric readers only ``xtrace.reduce``'s result and
removes the trace after loading it.  Importing this module therefore wraps
``xtrace.load`` once: the wrapper returns what ``xtrace.load`` returns,
unchanged, and keeps this module's reduction of the same file for the
readers (``for_ctx``).
"""
from __future__ import annotations

import dataclasses
import functools
import re
import sys
import traceback
from collections import defaultdict
from typing import Optional

from chipbench import xtrace as X

SCOPES = ("local_model", "local_precond", "precond_refresh",
          "local_correction", "upload_encode", "flush", "server_update")
UNSCOPED = "unscoped"
SPAN_PREFIX = "repro."
OUTSIDE = "outside"
CONTAINERS = ("while", "conditional", "call")
SCOPE_STAT = "tf_op"
PROGRAM_STAT = "program_id"


@dataclasses.dataclass(frozen=True)
class Op:
    """A device op event with what its metadata say about it."""
    event: X.Event
    path: str = ""        # the op's name-scope path ("" where none)
    opcode: str = ""
    program: str = ""     # the id of the program the op belongs to


@dataclasses.dataclass
class Attribution:
    window_s: float
    n_devices: int
    scoped: bool                  # some op carried a name of SCOPES
    has_spans: bool               # the host plane held repro.* spans
    device_by_scope: dict         # scope or UNSCOPED -> seconds a chip
    idle_by_span: dict            # phase or OUTSIDE -> seconds a chip
    unscoped_in_program: float    # UNSCOPED seconds in scoped programs
    unscoped_top: list            # [[op, path, seconds]] of those, longest


# ------------------------------------------------------- names and paths

_OPCODE = re.compile(r"(?:^| )([a-z][a-z0-9_\-]*)\(")
_NAME = re.compile(r"([A-Za-z_][\w\-.]*)\)*$")


def opcode(hlo_text: str) -> str:
    """``%cond.3 = (f32[2]{0}) conditional(s32[] %p, ...)`` ->
    ``conditional``: the first word before a parenthesis after the type."""
    rhs = hlo_text.split(" = ", 1)[-1]
    m = _OPCODE.search(rhs)
    return m.group(1) if m else ""


def scope_of(path: str) -> Optional[str]:
    """The innermost name of ``SCOPES`` on a name-scope path; a transformed
    scope counts as its name (``transpose(jvp(local_model))``)."""
    for part in reversed(path.split("/")):
        m = _NAME.search(part)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


# --------------------------------------------------- the XSpace protobuf

def _xspace_class():
    """A message class for the part of ``tsl/profiler/protobuf/xplane.proto``
    read here; the fields left out parse as unknown fields, and each map
    is read as its repeated key/value entries."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fd = descriptor_pb2.FileDescriptorProto(
        name="chipbench_xplane.proto", package="chipbench", syntax="proto3")

    def message(name, *fields):
        m = fd.message_type.add(name=name)
        for fname, number, ftype, repeated in fields:
            f = m.field.add(name=fname, number=number,
                            label=F.LABEL_REPEATED if repeated
                            else F.LABEL_OPTIONAL)
            if isinstance(ftype, str):
                f.type, f.type_name = F.TYPE_MESSAGE, ".chipbench." + ftype
            else:
                f.type = ftype

    message("XStat", ("metadata_id", 1, F.TYPE_INT64, False),
            ("uint64_value", 3, F.TYPE_UINT64, False),
            ("int64_value", 4, F.TYPE_INT64, False),
            ("str_value", 5, F.TYPE_STRING, False),
            ("ref_value", 7, F.TYPE_UINT64, False))
    message("XEvent", ("metadata_id", 1, F.TYPE_INT64, False))
    message("XLine", ("name", 2, F.TYPE_STRING, False),
            ("events", 4, "XEvent", True))
    message("XEventMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False),
            ("stats", 5, "XStat", True))
    message("XStatMetadata", ("id", 1, F.TYPE_INT64, False),
            ("name", 2, F.TYPE_STRING, False))
    message("EventMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XEventMetadata", False))
    message("StatMetadataEntry", ("key", 1, F.TYPE_INT64, False),
            ("value", 2, "XStatMetadata", False))
    message("XPlane", ("name", 2, F.TYPE_STRING, False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "EventMetadataEntry", True),
            ("stat_metadata", 5, "StatMetadataEntry", True))
    message("XSpace", ("planes", 1, "XPlane", True))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("chipbench.XSpace"))


def _stat_value(stat, stat_names: dict) -> str:
    if stat.str_value:
        return stat.str_value
    if stat.ref_value:
        return stat_names.get(stat.ref_value, "")
    return str(stat.uint64_value or stat.int64_value)


def device_op_metadata(path: str) -> dict:
    """For each device plane: one (hlo text, scope path, program id) per
    event of its ``XLA Ops`` lines, in the order ``xtrace.load`` reads
    them."""
    space = _xspace_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(X.DEVICE_PREFIX):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {}
        for entry in plane.event_metadata:
            md = entry.value
            stats = {stat_names.get(s.metadata_id): _stat_value(s, stat_names)
                     for s in md.stats}
            meta[entry.key] = (md.name,
                               stats.get(SCOPE_STAT, "").rsplit(":", 1)[0],
                               stats.get(PROGRAM_STAT, ""))
        out[plane.name] = [meta.get(e.metadata_id, ("", "", ""))
                           for line in plane.lines if line.name == X.OPS_LINE
                           for e in line.events]
    return out


def host_spans(path: str) -> list:
    """The program's ``repro.*`` spans of the host plane, as ``xtrace``
    reads the benchmark's own."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    return [X.Event(e.name, e.start_ns, e.duration_ns)
            for plane in pd.planes if plane.name == X.HOST_PLANE
            for line in plane.lines for e in line.events
            if e.name.startswith(SPAN_PREFIX)]


def scoped_ops(trace: X.Trace, metadata: dict) -> dict:
    """``trace``'s device ops with their metadata; a plane whose events do
    not line up with the metadata one for one keeps bare ops."""
    out = {}
    for dev, events in trace.device_ops.items():
        md = metadata.get(dev, [])
        if len(md) != len(events) or any(
                X.short_name(text) != e.name
                for e, (text, _, _) in zip(events, md)):
            _log(f"scopes: the op metadata of {dev} do not line up with "
                 f"its {len(events)} events; its ops stay unscoped")
            out[dev] = [Op(e) for e in events]
            continue
        out[dev] = [Op(e, p, opcode(text), prog)
                    for e, (text, p, prog) in zip(events, md)]
    return out


# ----------------------------------------------------------- reductions

def _seconds(intervals, lo, hi) -> float:
    return sum(e - s for s, e in X.merged(intervals, lo, hi)) * 1e-9


def attribute(ops: dict, spans: list, window: tuple) -> Attribution:
    """``ops``: device plane -> [Op]; ``spans``: the ``repro.*`` host spans;
    ``window``: (start, end) in the trace's nanoseconds."""
    lo, hi = window
    devices = [d for d, o in ops.items() if o]
    n = max(len(devices), 1)
    by_scope, idle = defaultdict(float), defaultdict(float)
    unscoped_in_program = 0.0
    unscoped_ops = defaultdict(float)
    scoped = False
    for dev in devices:
        inside = [o for o in ops[dev]
                  if o.event.end_ns > lo and o.event.start_ns < hi]
        busy = X.merged(((o.event.start_ns, o.event.end_ns)
                         for o in inside), lo, hi)
        for s, e in X.gaps(busy, lo, hi):
            idle[label_at((s + e) / 2, spans)] += (e - s) * 1e-9
        leaves = defaultdict(list)
        for o in inside:
            if o.opcode not in CONTAINERS:
                leaves[scope_of(o.path) or UNSCOPED].append(o)
        scoped = scoped or any(k != UNSCOPED for k in leaves)
        programs = {o.program for k, group in leaves.items()
                    if k != UNSCOPED for o in group}
        for key, group in leaves.items():
            by_scope[key] += _seconds(
                ((o.event.start_ns, o.event.end_ns) for o in group), lo, hi)
        for o in leaves.get(UNSCOPED, ()):
            if o.program in programs:
                secs = (min(o.event.end_ns, hi)
                        - max(o.event.start_ns, lo)) * 1e-9
                unscoped_ops[(o.event.name, o.path)] += secs
                unscoped_in_program += secs
    top = sorted(unscoped_ops.items(), key=lambda kv: -kv[1])[:5]
    return Attribution(
        window_s=(hi - lo) * 1e-9, n_devices=len(devices),
        scoped=scoped, has_spans=bool(spans),
        device_by_scope={k: v / n for k, v in by_scope.items()},
        idle_by_span={k: v / n for k, v in idle.items()},
        unscoped_in_program=unscoped_in_program / n,
        unscoped_top=[[name, path, s / n] for (name, path), s in top])


def label_at(t: float, spans: list) -> str:
    """The phase of the innermost (shortest) program span open at ``t``."""
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns]
    if not open_:
        return OUTSIDE
    return min(open_, key=lambda s: s.dur_ns).name[len(SPAN_PREFIX):]


def attribute_file(path: str, trace: X.Trace) -> Attribution:
    """The attribution of a trace file that ``xtrace.load`` read as
    ``trace``, over the benchmark's window."""
    return attribute(scoped_ops(trace, device_op_metadata(path)),
                     host_spans(path), X.window_of(trace))


# ------------------------------------------------ the readers' interface

_LAST: dict = {}


def _log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def _keep_beside_load() -> None:
    load = X.load
    if getattr(load, "keeps_scopes", False):
        return

    @functools.wraps(load)
    def load_and_attribute(path):
        trace = load(path)
        _LAST.clear()
        try:
            _LAST["attribution"] = attribute_file(path, trace)
        except Exception:  # noqa: BLE001 - the run goes on; readers find
            # nothing and the traceback says why
            _log(f"scopes: no attribution of {path}:\n"
                 + traceback.format_exc())
        return trace

    load_and_attribute.keeps_scopes = True
    X.load = load_and_attribute


_keep_beside_load()


def for_ctx(ctx) -> Optional[Attribution]:
    """The attribution of the trace ``ctx.reduced`` was reduced from, or
    None; logs its breakdown, in ms a round, the first time it is read."""
    att = _LAST.get("attribution")
    r = ctx.reduced
    if att is None or not r.n_devices or ctx.rounds <= 0 or (
            att.window_s, att.n_devices) != (r.window_s, r.n_devices):
        return None
    if not _LAST.get("logged"):
        _LAST["logged"] = True
        per = 1e3 / ctx.rounds
        _log("scopes: ms a round: device_by_scope=" + repr(
            {k: v * per for k, v in sorted(att.device_by_scope.items())})
            + " idle_by_span=" + repr(
            {k: v * per for k, v in sorted(att.idle_by_span.items())})
            + f" unscoped_in_program={att.unscoped_in_program * per!r}"
            + " unscoped_top=" + repr(
            [[n, p, s * per] for n, p, s in att.unscoped_top]))
    return att


def scope_ms(ctx, *scopes) -> Optional[float]:
    """Device ms a round in the given scopes; None without scopes."""
    att = for_ctx(ctx)
    if att is None or not att.scoped:
        return None
    return 1e3 * sum(att.device_by_scope.get(s, 0.0)
                     for s in scopes) / ctx.rounds


def idle_ms(ctx, *phases) -> Optional[float]:
    """Device idle ms a round under the given program spans; None without
    program spans."""
    att = for_ctx(ctx)
    if att is None or not att.has_spans:
        return None
    return 1e3 * sum(att.idle_by_span.get(p, 0.0)
                     for p in phases) / ctx.rounds
