"""Batch staging shared by the sync and async runtimes.

``client_batch_fn(cid, rng)`` yields one local minibatch; staging stacks the
K per-step batches (and, for a synchronous cohort, the S clients) into
leading (S, K, ...) axes with as few device transfers as possible:

  * batch fn yields host (numpy) arrays -> stack entirely on host with
    ``np.stack`` and do a *single* device transfer per leaf;
  * batch fn yields device (jax) arrays -> stack on device with
    ``jnp.stack``; pulling them back to host first would add S*K
    device-to-host copies just to save the stack.

Reusable host buffers (``StagingBuffers``) take the host path one step
further: the (S, K, ...) per-leaf arrays are allocated once and refilled
in place every round, so steady-state staging does zero large host
allocations.  The chunk-streaming pipeline (``fed.pipeline``) stages into
these buffers row-by-row from a background thread pool.

Thread-safety contract
----------------------

Under the background stager a ``client_batch_fn`` may be called from
worker threads, concurrently for different clients.  A fn is safe to call
concurrently iff it is a pure function of ``(cid, rng)`` — it must not
mutate shared Python state (the rng passed in is private to the client).
Mark such fns with ``mark_thread_safe``; the built-in scenario batch fns
are marked.  Unmarked fns are *serialized* through a module lock — always
correct, just without intra-chunk staging parallelism.
"""
from __future__ import annotations

import threading

import numpy as np
import jax
import jax.numpy as jnp

_UNSAFE_FN_LOCK = threading.Lock()


def mark_thread_safe(fn):
    """Declare ``fn`` safe for concurrent calls (a pure function of its
    arguments).  Returns ``fn`` so it works as a decorator."""
    fn._repro_thread_safe = True
    return fn


def is_thread_safe(fn) -> bool:
    return bool(getattr(fn, "_repro_thread_safe", False))


def serialized_unless_thread_safe(fn):
    """Call-through wrapper enforcing the staging contract: unmarked fns
    run under a module-wide lock so concurrent stager workers cannot
    corrupt shared state they might mutate."""
    if is_thread_safe(fn):
        return fn

    def locked(*a, **kw):
        with _UNSAFE_FN_LOCK:
            return fn(*a, **kw)
    return locked


def _stacker(tree):
    """np.stack when every leaf is host-side, else jnp.stack."""
    on_host = all(isinstance(leaf, np.ndarray) or np.isscalar(leaf)
                  for leaf in jax.tree.leaves(tree))
    return np.stack if on_host else jnp.stack


def _stack_steps(client_batch_fn, cid: int, local_steps: int, rng):
    """One client's K per-step batches stacked to a (K, ...) pytree."""
    steps = [client_batch_fn(int(cid), rng) for _ in range(local_steps)]
    stack = _stacker(steps[0])
    return jax.tree.map(lambda *xs: stack(xs), *steps)


def stage_client_batches(client_batch_fn, cid: int, local_steps: int, rng):
    """One client's round of batches, stacked to leading (K, ...) axes."""
    return jax.tree.map(
        jnp.asarray, _stack_steps(client_batch_fn, cid, local_steps, rng))


# ---------------------------------------------------------- host buffers

class StagingBuffers:
    """Preallocated, reusable (S, K, ...) host buffers for batch staging.

    One buffer tree per requested ``(tag, s)`` key, allocated lazily from
    the first staged client's leaf shapes/dtypes and refilled in place on
    every later round — steady-state staging allocates nothing large.
    Rows are written independently (``fill_row``), so disjoint clients can
    be filled from concurrent stager workers.
    """

    def __init__(self):
        self._bufs: dict = {}
        # concurrent stager workers race on lazy allocation: without the
        # lock two callers could each build a tree and fill different ones
        self._lock = threading.Lock()

    def get(self, key, s: int, template):
        """The (S, ...) buffer tree for ``(key, s)``; ``template`` is one
        client's stacked (K, ...) pytree (host or device leaves)."""
        with self._lock:
            buf = self._bufs.get((key, s))
            if buf is None:
                buf = jax.tree.map(
                    lambda x: np.empty((s, *np.shape(x)),
                                       dtype=np.asarray(x).dtype), template)
                self._bufs[(key, s)] = buf
        return buf

    def peek(self, key, s: int):
        """The already-allocated buffer tree for ``(key, s)`` (KeyError if
        no client was staged into it yet)."""
        with self._lock:
            return self._bufs[(key, s)]

    @staticmethod
    def fill_row(buf, i: int, row):
        """Write one client's (K, ...) pytree into row ``i`` in place."""
        jax.tree.map(lambda b, r: b.__setitem__(i, np.asarray(r)), buf, row)


def produce_cohort_batches(client_batch_fn, cohort, local_steps: int, rng):
    """Each cohort client's K per-step batches, stacked to (K, ...) pytrees
    (the per-client half of ``stage_cohort_batches``)."""
    return [_stack_steps(client_batch_fn, cid, local_steps, rng)
            for cid in cohort]


def stack_cohort_batches(per_client, buffers: StagingBuffers | None = None):
    """Per-client (K, ...) pytrees stacked to leading (S, K, ...) axes on
    the device (the cohort half of ``stage_cohort_batches``)."""
    stack = _stacker(per_client[0])
    if buffers is not None and stack is np.stack:
        buf = buffers.get("cohort", len(per_client), per_client[0])
        for i, row in enumerate(per_client):
            StagingBuffers.fill_row(buf, i, row)
        return jax.tree.map(jnp.asarray, buf)
    stacked = jax.tree.map(lambda *xs: stack(xs), *per_client)
    return jax.tree.map(jnp.asarray, stacked)


def stage_cohort_batches(client_batch_fn, cohort, local_steps: int, rng,
                         buffers: StagingBuffers | None = None):
    """A cohort's batches, stacked to leading (S, K, ...) axes.

    With ``buffers``, host-side batch fns refill a persistent buffer tree
    instead of re-allocating a fresh ``np.stack`` per round (values are
    identical — same rows, one device upload per leaf either way).
    Device-side batch fns keep the ``jnp.stack`` path: their leaves are
    already on device and a host bounce would add S*K transfers.
    """
    return stack_cohort_batches(
        produce_cohort_batches(client_batch_fn, cohort, local_steps, rng),
        buffers)
