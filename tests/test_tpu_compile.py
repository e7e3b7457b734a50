"""Compile guards: the Pallas kernels compiled (not interpreted) for a
described TPU v5e: five at llama-60m widths (d_model 512, d_ff 1376), and
the panel kernel of SOAP's blocked Householder QR at the chip benchmark's
widest refresh matrices (ViT-S: 8 clients of 1,536 rows; SmolLM-360M: 4
stacked layers of 2,560 rows), with the blocked QR around it at three
panels.

No chip is needed: the TPU compiler compiles for a topology that is only
described.  The description happens inside the module fixture, never at
import, so every test worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_agg.kernel import dequant_accumulate
from repro.kernels.householder_qr import ops as hq_ops
from repro.kernels.householder_qr.kernel import factor_panel
from repro.kernels.ns_ortho.kernel import matmul_fused
from repro.kernels.qblock.kernel import quantize
from repro.kernels.soap_rotate.kernel import adam_moments
from repro.kernels.sophia_update.kernel import sophia_update

D_MODEL, D_FF = 512, 1376
COHORT = 4
QBLOCK = 128
N_BLOCKS = D_MODEL * D_FF // QBLOCK   # 5504: one MLP matrix in qblocks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001  (any failure means: no TPU compiler here)
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args, **static):
    return fn.lower(*args, interpret=False, **static).compile().as_text()


def test_qblock_quantize_compiles(one_chip):
    x = _spec(one_chip, (D_FF, D_MODEL))
    assert "tpu_custom_call" in _compiled_text(quantize, x, block=QBLOCK)


def test_fused_agg_dequant_accumulate_compiles(one_chip):
    q = _spec(one_chip, (COHORT, N_BLOCKS, QBLOCK), jnp.int8)
    scale = _spec(one_chip, (COHORT, N_BLOCKS))
    weights = _spec(one_chip, (COHORT,))
    assert "tpu_custom_call" in _compiled_text(dequant_accumulate, q, scale,
                                               weights)


def test_soap_rotate_adam_moments_compiles(one_chip):
    g, m, v = (_spec(one_chip, (D_MODEL, D_FF)) for _ in range(3))
    step = _spec(one_chip, (), jnp.int32)
    assert "tpu_custom_call" in _compiled_text(adam_moments, g, m, v,
                                               step=step)


def test_ns_ortho_matmul_fused_compiles(one_chip):
    a = _spec(one_chip, (D_MODEL, D_MODEL))
    x, aux = (_spec(one_chip, (D_MODEL, D_FF)) for _ in range(2))
    assert "tpu_custom_call" in _compiled_text(matmul_fused, a, x, aux,
                                               alpha=0.5, beta=2.0)


def test_sophia_update_compiles(one_chip):
    g, m, h = (_spec(one_chip, (D_MODEL, D_FF)) for _ in range(3))
    assert "tpu_custom_call" in _compiled_text(sophia_update, g, m, h)


@pytest.mark.parametrize("batch,rows", [(8, 1536), (4, 2560)])
def test_householder_qr_panel_compiles(one_chip, batch, rows):
    panel = _spec(one_chip, (batch, hq_ops.PANEL, rows))
    assert "tpu_custom_call" in _compiled_text(factor_panel, panel)


def test_householder_qr_blocked_compiles(one_chip):
    # three panels: the trailing update and the backward Q formation of
    # every panel position; the panel kernel's widest shapes are above
    shape = (2, 3 * hq_ops.PANEL, 3 * hq_ops.PANEL)
    assert hq_ops.route(shape, use_pallas=True) == "pallas"
    assert "tpu_custom_call" in _compiled_text(hq_ops.blocked_qr,
                                               _spec(one_chip, shape))
