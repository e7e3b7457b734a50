"""A wire kernel's share of its roofline, from the trace and from shapes.

The kernel runs once per Theta leaf for a round's whole cohort (the cohort
axis is batched into one call), possibly more than once per leaf.  Its
least time per round is the sum over Theta leaves of
``chipbench.flops.least_seconds`` of that call's operations and bytes; the
share is the least time of every call the window ran over the summed device
time of the kernel's events.
"""
from chipbench import flops as F
from chipbench.xtrace import kernel_name


def kernel_share(ctx, kernel: str, function: str,
                 clients: int | None = None) -> float | None:
    """``function``: the name of the Python function whose ``pallas_call``
    the kernel is (its ops are named after it); ``clients``: how many of the
    cohort's clients one call covers, the whole cohort unless given."""
    if ctx.traffic.get("theta_codec") != "qblock" or not ctx.theta_sizes:
        return None
    events = [e for e in ctx.reduced.ops
              if e.kernel and kernel_name(e.name) == function]
    if not events:
        return None
    calls = len(events) / (ctx.rounds * len(ctx.theta_sizes))
    if calls < 1 or calls != int(calls):
        return None       # the events do not map onto the leaves
    least = ctx.rounds * calls * F.kernel_least_seconds(
        kernel, ctx.theta_sizes, ctx.traffic["qblock_size"],
        clients or ctx.clients, ctx.peaks)
    spent = sum(e.dur_ns for e in events) * 1e-9
    return 100.0 * least / spent
