"""local_precond_ms: device time a round of the local optimizer's update,
the preconditioner refresh left out.

The union of the intervals of the leaf ops whose innermost named scope is
``local_precond`` (``core/client.py`` around ``opt.update``; for SOAP the
factor EMAs, the rotations and Adam in the eigenbasis), in the traced
window, over the rounds in it (``chipbench.scopes``).
"""
from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "local_precond")
