"""Reduce a profiler trace to device busy time, idle gaps and op totals.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<n>``; its ``XLA Ops`` line holds one
event per operation that ran, with a start and a duration in nanoseconds,
named by the op's HLO text (a Pallas kernel is a ``tpu_custom_call`` named
after the function that calls it).
The host plane ``/host:CPU`` holds the benchmark's own
``jax.profiler.TraceAnnotation`` spans, named ``bench.<phase>``, on the same
clock.

  busy      the union of a device's op intervals inside the window
  idle      the window minus busy; each gap is labelled with the innermost
            benchmark span that was open on the host at its midpoint
  op time   the summed durations of a device's ops, by op name; ops nest
            (a loop's event spans its body's), so op times overlap
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    kernel: bool = False     # a Pallas kernel (a ``tpu_custom_call``)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict     # device plane name -> [Event]
    host_spans: list     # [Event] named bench.*


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(hlo_text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    head = hlo_text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def kernel_name(op: str) -> str:
    """The function a Pallas kernel's op is named after:
    ``vmap_jit_quantize__.7`` -> ``quantize``."""
    base = re.sub(r"\.\d+$", "", op).strip("_")
    while True:
        for prefix in ("vmap_", "jit_"):
            if base.startswith(prefix):
                base = base[len(prefix):].strip("_")
                break
        else:
            return base


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, spans = defaultdict(list), []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[plane.name].extend(
                        Event(short_name(e.name), e.start_ns, e.duration_ns,
                              KERNEL_MARK in e.name)
                        for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend(Event(e.name, e.start_ns, e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(dict(device_ops), spans)


def merged(intervals, lo: float, hi: float) -> list:
    """Sorted disjoint [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The [start, end) intervals of [lo, hi) not covered by ``busy``."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label_at(t: float, spans: list) -> str:
    """The innermost (shortest) benchmark span open at time ``t``."""
    open_ = [s for s in spans if s.start_ns <= t < s.end_ns
             and s.name != WINDOW_SPAN]
    if not open_:
        return "outside any phase"
    return min(open_, key=lambda s: s.dur_ns).name[len(SPAN_PREFIX):]


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                 # mean over the devices used
    n_devices: int
    op_seconds: dict              # op name -> seconds, summed over devices
    idle_gaps: list               # [(label, seconds)], longest first
    ops: list                     # [Event] of every device, in the window


def window_of(trace: Trace) -> tuple:
    wins = [s for s in trace.host_spans if s.name == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    w = max(wins, key=lambda s: s.dur_ns)
    return w.start_ns, w.end_ns


def reduce(trace: Trace, window: Optional[tuple] = None) -> Reduced:
    lo, hi = window if window is not None else window_of(trace)
    busy_total, op_seconds, all_gaps, ops = 0.0, defaultdict(float), [], []
    devices = [d for d, evs in trace.device_ops.items() if evs]
    for dev in devices:
        inside = [e for e in trace.device_ops[dev]
                  if e.end_ns > lo and e.start_ns < hi]
        ops.extend(inside)
        busy = merged(((e.start_ns, e.end_ns) for e in inside), lo, hi)
        busy_total += sum(e - s for s, e in busy)
        for e in inside:
            op_seconds[e.name] += (min(e.end_ns, hi)
                                   - max(e.start_ns, lo)) * 1e-9
        all_gaps.extend(gaps(busy, lo, hi))
    labelled = sorted(((label_at((s + e) / 2, trace.host_spans),
                        (e - s) * 1e-9) for s, e in all_gaps),
                      key=lambda x: -x[1])
    n = max(len(devices), 1)
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
                   n_devices=len(devices), op_seconds=dict(op_seconds),
                   idle_gaps=labelled, ops=ops)


def top(items, k: int = 10) -> list:
    return [[name, sec] for name, sec in
            sorted(items, key=lambda x: -x[1])[:k]]
