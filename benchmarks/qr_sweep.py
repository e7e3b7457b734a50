"""Sweep of the blocked Householder QR against XLA's QR on a TPU.

    python benchmarks/qr_sweep.py [--out qr_sweep.json]

Two parts, each timing the mean of ``--iters`` back-to-back calls after a
warm-up call, ended by ``block_until_ready``:

1. ``small``: XLA's QR against the blocked QR on small square matrices, to
   choose the row threshold of ``ops.route``.
2. ``cell``: XLA's QR against the blocked QR on the largest refresh matrix
   of each cell of the chip benchmark (ViT-S: 8 clients of 1,536 rows;
   SmolLM-360M: 4 stacked layers of 2,560 rows), with the blocked Q's
   largest gap to XLA's Q up to column signs (full-rank Gaussian input) and
   its orthonormality on a rank-deficient PSD input (``G G^T`` of half
   rank, as SOAP's factors are).

Refuses any platform but ``tpu``: a CPU timing says nothing of the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels.householder_qr import ops  # noqa: E402

HIGHEST = jax.lax.Precision.HIGHEST
SMALL = [(8, 128, 128), (8, 256, 256), (4, 320, 320), (8, 384, 384)]
CELL = [(8, 1536, 1536), (4, 2560, 2560)]


def _ms(fn, x, iters: int) -> float:
    jax.block_until_ready(fn(x))
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn(x)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / iters


def _orth(q) -> float:
    eye = jnp.eye(q.shape[-1], dtype=jnp.float32)
    return float(jnp.max(jnp.abs(
        jnp.einsum("bki,bkj->bij", q, q, precision=HIGHEST) - eye)))


def _gap(q, q_ref) -> float:
    sign = jnp.sign(jnp.sum(q * q_ref, axis=-2, keepdims=True))
    return float(jnp.max(jnp.abs(q * sign - q_ref)))


def _blocked(s):
    return ops.blocked_qr(s)[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="qr_sweep.json")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"qr_sweep: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    key = jax.random.key(0)
    xla = jax.jit(lambda s: jnp.linalg.qr(s)[0])
    out = {"device": dev.device_kind, "small": [], "cell": []}

    for shape in SMALL:
        x = jax.random.normal(key, shape, jnp.float32)
        row = {"shape": list(shape), "xla_ms": _ms(xla, x, args.iters),
               "blocked_ms": _ms(_blocked, x, args.iters)}
        print("small", json.dumps(row), flush=True)
        out["small"].append(row)

    for shape in CELL:
        x = jax.random.normal(key, shape, jnp.float32)
        g = jax.random.normal(key, (*shape[:-1], shape[-1] // 2), jnp.float32)
        psd = jnp.einsum("bik,bjk->bij", g, g, precision=HIGHEST)
        row = {"shape": list(shape), "xla_ms": _ms(xla, x, args.iters),
               "blocked_ms": _ms(_blocked, x, args.iters),
               "gap": _gap(_blocked(x), xla(x)),
               "orth_psd": _orth(_blocked(psd)),
               "xla_orth_psd": _orth(xla(psd))}
        print("cell", json.dumps(row), flush=True)
        out["cell"].append(row)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
