"""precond_refresh_ms: device time a round of the curvature refresh.

The union of the intervals of the leaf ops whose innermost named scope is
``precond_refresh`` (``optim/soap.py``: the eigenbasis refresh in the
``lax.cond`` branch; Sophia's Hutchinson estimate), in the traced window,
over the rounds in it (``chipbench.scopes``).
"""
from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "precond_refresh")
