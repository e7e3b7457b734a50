"""local_model_ms: device time a round of the client model's forward and
backward.

The union of the intervals of the leaf ops whose innermost named scope is
``local_model`` (``core/client.py``: ``jax.value_and_grad`` of the loss),
in the traced window, over the rounds in it (``chipbench.scopes``).
"""
from chipbench.scopes import scope_ms


def read(ctx):
    return scope_ms(ctx, "local_model")
