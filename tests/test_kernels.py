"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles,
executed in interpret mode on CPU (deliverable c)."""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.ns_ortho import ops as ns_ops, ref as ns_ref
from repro.kernels.ns_ortho.kernel import matmul_fused
from repro.kernels.sophia_update import ops as so_ops, ref as so_ref
from repro.kernels.soap_rotate import ops as sr_ops, ref as sr_ref
from repro.kernels.soap_rotate.kernel import adam_moments
from repro.kernels.qblock import ops as qb_ops, ref as qb_ref
from repro.kernels.householder_qr import ops as hq_ops, ref as hq_ref

KEY = jax.random.key(7)

MM_SHAPES = [(8, 8, 8), (128, 128, 128), (64, 200, 96), (130, 257, 50),
             (256, 64, 384)]


@pytest.mark.parametrize("m,k,n", MM_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_fused(m, k, n, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    lhs = jax.random.normal(k1, (m, k), dtype)
    rhs = jax.random.normal(k2, (k, n), dtype)
    aux = jax.random.normal(k3, (m, n), dtype)
    got = matmul_fused(lhs, rhs, aux, alpha=0.5, beta=-2.0, interpret=True)
    want = (0.5 * (lhs.astype(jnp.float32) @ rhs.astype(jnp.float32))
            - 2.0 * aux.astype(jnp.float32)).astype(dtype)
    tol = 1e-5 if dtype == jnp.float32 else 6e-2
    assert jnp.max(jnp.abs(got.astype(jnp.float32)
                           - want.astype(jnp.float32))) < tol * max(1, k ** 0.5)


@pytest.mark.parametrize("shape", [(32, 48), (128, 128), (96, 250), (257, 64)])
def test_newton_schulz_pallas_matches_ref(shape):
    g = jax.random.normal(KEY, shape, jnp.float32)
    want = ns_ref.newton_schulz(g)
    got = ns_ops.newton_schulz_pallas(g, interpret=True)
    assert jnp.max(jnp.abs(want - got)) < 1e-4


def test_newton_schulz_singular_values_near_one():
    g = jax.random.normal(KEY, (64, 128), jnp.float32)
    y = ns_ref.newton_schulz(g)
    s = jnp.linalg.svd(y, compute_uv=False)
    assert float(s.max()) < 1.35 and float(s.min()) > 0.45


@pytest.mark.parametrize("shape", [(17,), (64, 64), (3, 40, 50), (2048,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sophia_update_kernel(shape, dtype):
    k1, k2, k3 = jax.random.split(KEY, 3)
    g = jax.random.normal(k1, shape, dtype)
    m = jax.random.normal(k2, shape, jnp.float32)
    h = jax.random.uniform(k3, shape, jnp.float32)
    d_ref, m_ref = so_ref.sophia_update(g, m, h)
    d_pal, m_pal = so_ops.sophia_update(g, m, h, use_pallas=True,
                                        interpret=True)
    assert jnp.max(jnp.abs(d_ref - d_pal)) < 1e-5
    assert jnp.max(jnp.abs(m_ref - m_pal)) < 1e-5
    assert float(jnp.max(jnp.abs(d_pal))) <= 0.05 + 1e-6  # clip bound


@pytest.mark.parametrize("m,n", [(16, 24), (128, 128), (100, 60)])
def test_soap_rotate_kernel(m, n):
    ks = jax.random.split(KEY, 5)
    g = jax.random.normal(ks[0], (m, n), jnp.float32)
    ql, _ = jnp.linalg.qr(jax.random.normal(ks[1], (m, m)))
    qr_, _ = jnp.linalg.qr(jax.random.normal(ks[2], (n, n)))
    mm = jax.random.normal(ks[3], (m, n))
    vv = jax.random.uniform(ks[4], (m, n))
    want = sr_ref.soap_rotated_update(g, ql, qr_, mm, vv)
    got = sr_ops.soap_rotated_update(g, ql, qr_, mm, vv, use_pallas=True,
                                     interpret=True)
    for w, o in zip(want, got):
        assert jnp.max(jnp.abs(w - o)) < 5e-5
    # bias-corrected variant (step may be a traced scalar — see optim.soap)
    want_bc = sr_ref.soap_rotated_update(g, ql, qr_, mm, vv,
                                         step=jnp.int32(2))
    got_bc = sr_ops.soap_rotated_update(g, ql, qr_, mm, vv,
                                        step=jnp.int32(2), use_pallas=True,
                                        interpret=True)
    for w, o in zip(want_bc, got_bc):
        assert jnp.max(jnp.abs(w - o)) < 5e-5
    assert jnp.max(jnp.abs(want_bc[0] - want[0])) > 1e-3  # correction bites


@pytest.mark.parametrize("shape", [(17,), (128,), (64, 64), (3, 40, 50),
                                   (4096,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_qblock_kernel_matches_ref(shape, dtype):
    x = 3.0 * jax.random.normal(KEY, shape, dtype)
    q_ref, s_ref = qb_ref.quantize(x, block=128)
    q_pal, s_pal = qb_ops.quantize(x, block=128, use_pallas=True,
                                   interpret=True)
    assert q_pal.dtype == jnp.int8 and q_ref.shape == q_pal.shape
    assert jnp.array_equal(q_ref, q_pal)
    assert jnp.max(jnp.abs(s_ref - s_pal)) < 1e-7
    # dequantized error bounded by half a step per block
    x_hat = qb_ref.dequantize(q_pal, s_pal, x.shape)
    err = jnp.abs(x_hat - x.astype(jnp.float32)).reshape(-1)
    bound = jnp.repeat(s_pal / 2, 128)[: err.size]
    assert bool(jnp.all(err <= bound + 1e-6))


def test_qblock_kernel_rejects_bad_block():
    with pytest.raises(ValueError, match="multiple of 128"):
        qb_ops.quantize(jnp.ones((8,)), block=100, use_pallas=True,
                        interpret=True)


@pytest.mark.parametrize("shape", [(40,), (128, 256)])
def test_adam_moments_kernel(shape):
    ks = jax.random.split(KEY, 3)
    g = jax.random.normal(ks[0], shape)
    m = jax.random.normal(ks[1], shape)
    v = jax.random.uniform(ks[2], shape)
    n, m2, v2 = adam_moments(g, m, v, b1=0.9, b2=0.99, interpret=True)
    m_want = 0.9 * m + 0.1 * g
    v_want = 0.99 * v + 0.01 * g * g
    assert jnp.allclose(m2, m_want, atol=1e-6)
    assert jnp.allclose(v2, v_want, atol=1e-6)
    assert jnp.allclose(n, m_want / (jnp.sqrt(v_want) + 1e-8), atol=1e-5)


# (shape, rank): rank None is a full-rank Gaussian matrix, else the
# rank-deficient PSD G G^T of a (..., m, rank) G, as SOAP's factors are; no
# row count is a multiple of the 128-column panel but 256
HQR_CASES = [((40, 40), None), ((200, 200), None), ((256, 256), None),
             ((2, 130, 100), None), ((160, 160), 60), ((3, 96, 96), 40)]


@pytest.mark.parametrize("shape,rank", HQR_CASES)
def test_householder_qr_matches_ref(shape, rank):
    if rank is None:
        s = jax.random.normal(KEY, shape, jnp.float32)
    else:
        g = jax.random.normal(KEY, (*shape[:-1], rank), jnp.float32)
        s = jnp.einsum("...ik,...jk->...ij", g, g,
                       precision=jax.lax.Precision.HIGHEST)
    q, r = hq_ops.blocked_qr(s, interpret=True)
    hi = functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)
    eye = jnp.eye(shape[-1])
    assert q.shape == shape and r.shape == (*shape[:-2], shape[-1], shape[-1])
    assert float(jnp.max(jnp.abs(hi("...ki,...kj->...ij", q, q) - eye))) < 1e-5
    assert bool(jnp.all(jnp.tril(r, -1) == 0))
    scale = float(jnp.max(jnp.abs(s)))
    assert float(jnp.max(jnp.abs(hi("...ik,...kj->...ij", q, r) - s))) \
        < 1e-5 * scale
    if rank is None:  # the same Q as XLA's, up to column signs
        want = hq_ref.qr_q(s)
        sign = jnp.sign(jnp.sum(q * want, axis=-2, keepdims=True))
        assert float(jnp.max(jnp.abs(q * sign - want))) < 1e-4


def test_householder_qr_route_and_ref_off_tpu():
    assert hq_ops.route((8, 1536, 1536), use_pallas=True) == "pallas"
    assert hq_ops.route((8, 1536, 1536), use_pallas=False) == "xla"
    assert hq_ops.route((64, 64), use_pallas=True) == "xla"    # below MIN_ROWS
    with pytest.raises(ValueError, match="square"):   # SOAP's are square
        hq_ops.qr_q(jnp.zeros((512, 1024)))
    s = jax.random.normal(KEY, (2, 300, 300), jnp.float32)
    # off the TPU every shape takes XLA's QR, bitwise
    assert jnp.array_equal(hq_ops.qr_q(s), jnp.linalg.qr(s)[0])
