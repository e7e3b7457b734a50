"""Pure-jnp oracle for SOAP's eigenbasis orthogonalization: the orthonormal
factor of a QR decomposition, by XLA's own QR.  It is also the path off the
TPU and for shapes the blocked kernel does not take."""
from __future__ import annotations

import jax.numpy as jnp


def qr_q(s):
    """Q of the reduced QR of s: (..., m, n)."""
    return jnp.linalg.qr(s)[0]
