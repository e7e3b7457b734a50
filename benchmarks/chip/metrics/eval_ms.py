"""eval_ms: mean host time of a round's eval phase.

Reads the ``eval`` spans of the system's tracer: the jitted ``eval_fn`` on the
new server parameters and the copy of its numbers to the host.
"""
import statistics


def read(ctx):
    durs = [e["dur_s"] for e in ctx.spans
            if e.get("event") == "span" and e.get("phase") == "eval"]
    return 1e3 * statistics.mean(durs) if durs else None
