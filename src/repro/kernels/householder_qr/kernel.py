"""Pallas TPU kernel: Householder factorization of one QR panel in VMEM.

The panel is given transposed, ``(b, rows)``: panel column ``i`` is row
``i`` of the block and the matrix rows lie along the 128 lanes, so every
vector op of the per-column loop is lane-dense.  The whole panel stays in
VMEM while its ``b`` columns are reduced, with no HBM round trip per column.
Each column costs one reflector (LAPACK ``dlarfg`` convention, the sign
XLA's QR expander uses) and a rank-1 update of the panel's later columns
on the vector unit; T comes from the Gram matrix Y^T Y, one MXU product.

On a TPU v5e the loop is bound by each column's chain of dependent
reductions, not by the vector work: reducing 16, 32 or 64 columns at a time
and updating the rest of the panel on the MXU was no faster (PERF.md).

Outputs, per matrix of the batch (the grid runs over the batch):

  yt  (b, rows)  Householder vectors, unit diagonal, zeros above it
  t   (b, b)     compact-WY factor, upper triangular: H_0 ... H_{b-1} =
                 I - Y T Y^T with Y = yt^T
  rt  (b, b)     the panel's R block, transposed (``rt[k, i] = R[i, k]``)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
HIGHEST = lax.Precision.HIGHEST


def _dot_t(a, b):
    """a @ b.T"""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=HIGHEST,
                           preferred_element_type=jnp.float32)


def _unit_lower(p, rows, lane):
    """The Householder vectors stored below the diagonal of ``p``, with
    their unit diagonal; ``rows`` holds each row's panel column index."""
    return jnp.where(lane > rows, p, jnp.where(lane == rows, 1.0, 0.0))


def _t_factor(g, tau):
    """Compact-WY T from the Gram matrix G = Y^T Y and the taus (1, n):
    T[:i, i] = -tau_i T[:i, :i] G[:i, i], T[i, i] = tau_i."""
    n = g.shape[0]
    r = lax.broadcasted_iota(jnp.int32, (n, n), 0)
    c = lax.broadcasted_iota(jnp.int32, (n, n), 1)
    lane = lax.broadcasted_iota(jnp.int32, (1, n), 1)
    col_id = lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    def body(i, t):
        z = jnp.sum(jnp.where(r == i, g, 0.0), axis=0, keepdims=True)
        z = jnp.where(lane < i, z, 0.0)            # G[i, :i] = G[:i, i]
        tau_i = jnp.sum(jnp.where(lane == i, tau, 0.0), axis=1,
                        keepdims=True)
        col = -tau_i * jnp.sum(t * z, axis=1, keepdims=True)
        col = jnp.where(col_id == i, tau_i, col)
        return jnp.where(c == i, col, t)

    return lax.fori_loop(0, n, body, jnp.zeros((n, n), jnp.float32))


def _panel_kernel(a_ref, yt_ref, t_ref, rt_ref):
    b, rows_n = a_ref.shape
    yt_ref[...] = a_ref[...]
    lane = lax.broadcasted_iota(jnp.int32, (1, rows_n), 1)
    lane_b = lax.broadcasted_iota(jnp.int32, (1, b), 1)
    rows = lax.broadcasted_iota(jnp.int32, (b, 1), 0)

    def column(i, taus):
        p = yt_ref[...]
        x = yt_ref[pl.ds(i, 1), :]
        alpha = jnp.sum(jnp.where(lane == i, x, 0.0), axis=1, keepdims=True)
        sigma = jnp.sum(jnp.where(lane > i, x * x, 0.0), axis=1,
                        keepdims=True)
        mu = jnp.sqrt(alpha * alpha + sigma)
        flat = sigma == 0.0      # nothing below the diagonal: H = I
        beta = jnp.where(flat, alpha, jnp.where(alpha < 0.0, mu, -mu))
        tau = jnp.where(flat, 0.0, (beta - alpha) / beta)
        below = jnp.where(flat, 0.0, x / jnp.where(flat, 1.0, alpha - beta))
        v = jnp.where(lane > i, below, jnp.where(lane == i, 1.0, 0.0))
        w = jnp.sum(p * v, axis=1, keepdims=True)
        # row i keeps R above the diagonal, beta on it, v below it
        row_i = jnp.where(lane < i, x, jnp.where(lane == i, beta, below))
        yt_ref[...] = jnp.where(rows > i, p - (tau * w) * v,
                                jnp.where(rows == i, row_i, p))
        return jnp.where(lane_b == i, tau, taus)

    taus = lax.fori_loop(0, b, column, jnp.zeros((1, b), jnp.float32))
    p = yt_ref[...]
    y = _unit_lower(p, rows, lane)
    yt_ref[...] = y
    t_ref[...] = _t_factor(_dot_t(y, y), taus)
    rt_ref[...] = jnp.where(lane_b <= rows, p[:, :b], 0.0)


def _vmem_bytes(b: int, rows: int) -> int:
    """VMEM the kernel asks for: the double-buffered panel in and out, and
    room for the per-column temporaries."""
    return 4 * b * rows * 4 * 3 + 4 * b * b * 4 * 4


@functools.partial(jax.jit, static_argnames=("interpret",))
def factor_panel(panel_t, *, interpret: bool = False):
    """Householder-factor a batch of transposed panels.

    panel_t: (batch, b, rows) float32 with ``b`` a multiple of 8 and
    ``rows >= b``.  Returns ``(yt, t, rt)`` (module docstring)."""
    n_batch, b, rows = panel_t.shape
    if b % 8 or rows < b:
        raise ValueError(f"panel {panel_t.shape}: b a multiple of 8, "
                         "rows >= b")
    blk = lambda *s: pl.BlockSpec((None, *s), lambda i: (i, 0, 0))
    return pl.pallas_call(
        _panel_kernel,
        grid=(n_batch,),
        in_specs=[blk(b, rows)],
        out_specs=[blk(b, rows), blk(b, b), blk(b, b)],
        out_shape=[jax.ShapeDtypeStruct((n_batch, b, rows), jnp.float32),
                   jax.ShapeDtypeStruct((n_batch, b, b), jnp.float32),
                   jax.ShapeDtypeStruct((n_batch, b, b), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, _vmem_bytes(b, rows))),
        interpret=interpret,
    )(panel_t.astype(jnp.float32))
