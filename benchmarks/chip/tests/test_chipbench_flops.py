"""Model FLOPs and kernel envelopes against hand-counted shapes."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from chipbench import flops as F  # noqa: E402
from chipbench import peaks as P  # noqa: E402
from chipbench import spec as S  # noqa: E402


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def _ref(family):
    return S.load_module(os.path.join(BENCH, "references", family + ".py"),
                         "reference")


def test_vit_s_round_flops_by_hand():
    cfg = _load("configs", "vit-s4-cifar100")
    mix = _load("traffic", "c8_k10_qblock")
    # one image, forward: 12 blocks x [2*65*(4*384^2 + 2*384*1536)
    # + 4*65^2*384] + patch embedding 2*64*48*384 + head 2*384*100
    block = 2 * 65 * (4 * 384 ** 2 + 2 * 384 * 1536) + 4 * 65 ** 2 * 384
    fwd = 12 * block + 2 * 64 * 48 * 384 + 2 * 384 * 100
    assert fwd == 2_840_687_616
    ref = _ref("vit")
    assert ref.flops_per_sample(cfg, mix) == 3 * fwd
    # 8 clients x 10 steps x 32 images
    assert F.clients_per_round(mix) == 8
    assert F.round_model_flops(ref, cfg, mix) == 8 * 10 * 32 * 3 * fwd
    assert F.round_model_flops(ref, cfg, mix) == pytest.approx(21.8e12,
                                                               rel=1e-3)


def test_smollm_d4_round_flops_by_hand():
    cfg = _load("configs", "smollm-360m-d4")
    mix = _load("traffic", "c4_seq1024")
    # matrix parameters a token passes through: 4 layers x (q, o: 960^2;
    # k, v: 960*320; gate, up, down: 960*2560) + the tied head 960*49152
    n = 4 * (2 * 960 ** 2 + 2 * 960 * 320 + 3 * 960 * 2560) + 960 * 49152
    assert n == 86_507_520
    per_token = 6 * n + 12 * 4 * 1024 * 960
    ref = _ref("llama")
    assert ref.flops_per_sample(cfg, mix) == per_token
    assert F.clients_per_round(mix) == 4
    tokens = 4 * 5 * 4 * 1024
    assert F.round_model_flops(ref, cfg, mix) == tokens * per_token
    assert F.round_model_flops(ref, cfg, mix) == pytest.approx(46.39e12,
                                                               rel=1e-3)


def test_kernel_envelopes_by_hand():
    # 1000 elements in blocks of 128: 8 blocks, the last one partial
    assert F.qblock_work(1000, 128, 8) == (6 * 1000 * 8,
                                           8 * (4 * 1000 + 1000 + 4 * 8))
    assert F.fused_agg_work(1000, 128, 8) == (
        2 * 8 * 1000 + 8 * 8, 8 * 1000 + 4 * 8 * 8 + 4 * 1000)


def test_least_time_names_its_bound():
    v5e = P.for_kind("TPU v5 lite")
    t, bound = F.least_seconds(6 * 1000, 5032, v5e)
    assert bound == "memory" and t == pytest.approx(5032 / 819e9)
    t, bound = F.least_seconds(1e12, 1.0, v5e)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)
    total = F.kernel_least_seconds("qblock", [1000, 256], 128, 2, v5e)
    assert total == pytest.approx((2 * (5000 + 32) + 2 * (1280 + 8)) / 819e9)


def test_peaks_refuse_an_unknown_device():
    assert P.for_kind("TPU v5 lite").bf16_flops == 197e12
    assert P.for_kind("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(P.UnknownDeviceError, match="TPU v9"):
        P.for_kind("TPU v9")
    with pytest.raises(P.UnknownDeviceError):
        P.for_kind("cpu")
