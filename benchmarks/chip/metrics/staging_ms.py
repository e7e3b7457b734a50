"""staging_ms: mean host time of a round's staging phase.

Reads the ``staging`` spans that the system's own tracer
(``repro.obs.trace.Tracer``, in-memory sink) records around
``FederatedExperiment._stage_batches``: sampling the cohort and stacking its
(S, K, ...) batches onto the device.
"""
import statistics


def read(ctx):
    durs = [e["dur_s"] for e in ctx.spans
            if e.get("event") == "span" and e.get("phase") == "staging"]
    return 1e3 * statistics.mean(durs) if durs else None
