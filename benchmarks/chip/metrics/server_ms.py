"""server_ms: device time a round of the flush and the server update.

The union of the intervals of the leaf ops whose innermost named scope is
``flush`` (aggregation of the uploads and Theta, with the ``fused_agg``
kernel) plus those of ``server_update`` (client-state scatter, geometry
controller, telemetry), both in ``core/algorithms.py``'s round function,
in the traced window, over the rounds in it (``chipbench.scopes``).
"""
from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "flush", "server_update")
