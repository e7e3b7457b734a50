"""Blocked Householder QR for the TPU: the blocked loop around the panel
kernel.

``qr_q(s)`` is what SOAP's eigenbasis refresh calls, on square matrices
``P @ Q``.  Its route is a pure function of the shape (``route``): on the
TPU a matrix of at least ``MIN_ROWS`` rows takes the blocked QR below, a
smaller one ``ref.qr_q`` (XLA's QR).  Off the TPU every matrix takes
``ref``.

``blocked_qr(a)`` works on the transposed matrix, padded with zeros to a
multiple of ``PANEL`` (zero rows and columns give identity reflectors, so
the padding leaves Q's leading block exact).  For each panel of ``PANEL``
columns the kernel factors the panel in VMEM and returns its Householder
vectors Y, compact-WY T and R block; the trailing columns are then updated
as ``A <- A - Y T^T (Y^T A)``.  Q is formed by applying the block reflectors
to the identity, last panel first, so each product touches only the
trailing block.  Both steps are float32 matmuls at ``HIGHEST``, the
precision of the dots of XLA's QR expander.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.householder_qr import ref
from repro.kernels.householder_qr.kernel import LANES, factor_panel
from repro.utils import hw

PANEL = LANES          # columns a kernel call factors: the MXU's width
MIN_ROWS = 256         # below this XLA's QR is as fast (chip sweep, PERF.md)

_mm = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST,
                        preferred_element_type=jnp.float32)


def route(shape, use_pallas: bool) -> str:
    """``"pallas"`` (blocked QR) or ``"xla"`` (``ref``) for square matrices
    of ``shape`` (..., m, m)."""
    return "pallas" if use_pallas and shape[-1] >= MIN_ROWS else "xla"


def _pad_to(x: int, k: int) -> int:
    return -(-x // k) * k


# one jitted function: SOAP refreshes many same-shaped matrices, and each
# would otherwise trace the whole panel loop again
@functools.partial(jax.jit, static_argnames=("interpret",))
def blocked_qr(a, *, interpret: bool = False):
    """Reduced QR of a (..., m, n), m >= n, by blocked Householder
    reflections; returns (q (..., m, n), r (..., n, n))."""
    *batch, m, n = a.shape
    nb = math.prod(batch)
    rows, cols = _pad_to(m, PANEL), _pad_to(n, PANEL)
    at = jnp.swapaxes(a.reshape(nb, m, n).astype(jnp.float32), 1, 2)
    at = jnp.pad(at, ((0, 0), (0, cols - n), (0, rows - m)))
    panels = []
    for j in range(0, cols, PANEL):
        e = j + PANEL
        yt, t, rt = factor_panel(at[:, j:e, j:], interpret=interpret)
        panels.append((yt, t))
        at = at.at[:, j:e, j:e].set(rt)
        if e < cols:
            trail = at[:, e:, j:]
            w = _mm("bcr,bkr->bck", trail, yt)
            trail = trail - _mm("bck,bkr->bcr", _mm("bck,bkl->bcl", w, t), yt)
            at = at.at[:, e:, j:].set(trail)
    q = jnp.broadcast_to(jnp.eye(rows, cols, dtype=jnp.float32),
                         (nb, rows, cols))
    for idx in reversed(range(len(panels))):
        yt, t = panels[idx]
        j = idx * PANEL
        blk = q[:, j:, j:]
        z = _mm("bkl,blc->bkc", t, _mm("bkr,brc->bkc", yt, blk))
        q = q.at[:, j:, j:].set(blk - _mm("bkr,bkc->brc", yt, z))
    q = q[:, :m, :n].reshape(*batch, m, n)
    r = jnp.triu(jnp.swapaxes(at, 1, 2)[:, :n, :n]).reshape(*batch, n, n)
    return q, r


def qr_q(s):
    """Q of the QR of square matrices s (..., m, m), routed by ``route``."""
    if s.shape[-1] != s.shape[-2]:
        raise ValueError(f"qr_q takes square matrices, got {s.shape}")
    if route(s.shape, hw.default_use_pallas()) == "xla":
        return ref.qr_q(s)
    return blocked_qr(s, interpret=hw.default_interpret())[0]
