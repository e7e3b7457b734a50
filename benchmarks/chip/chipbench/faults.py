"""Faults planted under the timed path, by name (``run.py --fault``).

Each takes the experiment and replaces its round program with a broken one;
the harness then drives the rest of a run as usual, and the staged batches
the reference follows are those the sound program would have had.  A run
with a fault planted has to come out with ``correct`` false.
"""
from __future__ import annotations

import jax


def state_unchanged(exp):
    """A step that returns the server's state unchanged."""
    step = exp.round_fn

    def stuck(server, cstate, *rest):
        _, new_cstate, metrics = step(server, cstate, *rest)
        return server, new_cstate, metrics

    exp.round_fn = stuck


def half_batch(exp):
    """Half of every local batch left out, the mean taken over the rest."""
    step = exp.round_fn

    def halved(server, cstate, slots, batches, key):
        # batches carry (S, K, B, ...): keep the first half of B
        batches = jax.tree.map(lambda a: a[:, :, : a.shape[2] // 2],
                               batches)
        return step(server, cstate, slots, batches, key)

    exp.round_fn = halved


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
