"""A cell runs the algorithm its mix names, its optimizer set from the mix's
block named after that optimizer: every such algorithm builds from its mix
alone, a mix without the block is refused, and the committed mixes keep the
optimizer arguments they were measured with."""
import dataclasses
import json
import math
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import chipbench_cells as C  # noqa: E402
from chipbench import harness  # noqa: E402
from chipbench import peaks as P  # noqa: E402
from chipbench import spec as S  # noqa: E402
from chipbench import xtrace as X  # noqa: E402

BLOCKS = {
    "fedpac_soap": ("soap", C.SOAP),
    "fedpac_muon": ("muon", {"b1": 0.95, "ns_steps": 5}),
    "fedpac_sophia": ("sophia", {"b1": 0.965, "b2": 0.99, "rho": 0.04}),
    "fedavg": ("sgd", {"momentum": 0.0}),
}
# the six SOAP keys every cell's optimizer was built from before a mix's
# block was passed whole
SOAP_KEYS = ("b1", "b2", "eps", "precond_freq", "adam_b1", "adam_b2")


@pytest.fixture(scope="module")
def vit_cell(tmp_path_factory):
    root = C.make_tree(str(tmp_path_factory.mktemp("bench")))
    return S.load_cell("tiny_vit.q", repo_root=root)


def _family(cell):
    """The family's reference and binding (an algorithm with no reference
    of its own, such as FedPAC-Muon, still builds)."""
    fam = cell.config["family"]
    return tuple(S.load_module(os.path.join(S.BENCH_DIR, kind, fam + ".py"),
                               what)
                 for kind, what in (("references", "reference"),
                                    ("bindings", "binding")))


def _mix(algorithm, block):
    name, opts = block
    mix = {k: v for k, v in C.TINY_VIT_MIX.items() if k != "soap"}
    return dict(mix, algorithm=algorithm, **{name: opts})


@pytest.mark.parametrize("algorithm", sorted(BLOCKS))
def test_each_algorithm_builds_from_its_own_block(vit_cell, algorithm,
                                                  monkeypatch):
    import repro.optim
    made = []
    make = repro.optim.make

    def recording(name, **kw):
        made.append((name, kw))
        return make(name, **kw)

    monkeypatch.setattr(repro.optim, "make", recording)
    cell = dataclasses.replace(
        vit_cell, traffic=_mix(algorithm, BLOCKS[algorithm]))
    ref, binding = _family(cell)
    exp = harness.build(cell, C.TEST_SEED, ref, binding)
    assert made == [BLOCKS[algorithm]]
    assert math.isfinite(exp.run_round()["loss"])


def test_a_mix_without_the_block_is_refused(vit_cell):
    mix = dict(C.TINY_FEDAVG_MIX)
    del mix["sgd"]
    cell = dataclasses.replace(vit_cell, traffic=mix)
    ref, binding = _family(cell)
    with pytest.raises(KeyError, match="'sgd'.*fedavg|fedavg.*'sgd'"):
        harness.build(cell, C.TEST_SEED, ref, binding)


def _committed(mix):
    with open(os.path.join(S.BENCH_DIR, "traffic", mix + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("mix", ["c8_k10_qblock", "c4_seq1024"])
def test_committed_mixes_keep_their_optimizer_arguments(mix):
    traffic = _committed(mix)
    assert harness.optimizer_kwargs(traffic) == {
        k: traffic["soap"][k] for k in SOAP_KEYS}


def test_the_chunked_executor_splits_the_encode_over_calls():
    """qblock_roofline: under the chunked executor each call encodes a chunk
    of the cohort, so the calls' least time counts that chunk's clients."""
    read = {m.name: m.read
            for m in S.load_cell("vit_s16.c16_k2_qblock").per_layer}
    # 2 rounds, 1 leaf, 4 chunks of 2 of the 8 clients: 8 calls of 10 us
    ops = [X.Event("vmap_jit_quantize__.3", 50e6 + i * 1e6, 10e3,
                   kernel=True) for i in range(8)]
    trace = X.Trace({"/device:TPU:0": ops},
                    [X.Event("bench.window", 0, 100e6)])
    ctx = types.SimpleNamespace(
        reduced=X.reduce(trace), spans=[], rounds=2,
        peaks=P.for_kind("TPU v5 lite"), round_flops=1e12,
        theta_sizes=[1000], clients=8, config={},
        traffic={"theta_codec": "qblock", "qblock_size": 128,
                 "executor": "chunked", "chunk_size": 2})
    least = 2 * (5000 + 32) / 819e9          # memory-bound, per call
    assert read["qblock_roofline"](ctx) == pytest.approx(
        100 * 8 * least / 80e-6)


@pytest.mark.parametrize("mix", ["c16_k2_qblock", "c8_k10_fedavg"])
def test_the_new_mixes_run_a_round_on_the_tiny_vit(vit_cell, mix):
    """The committed mix, its algorithm, optimizer block, executor and
    codecs as they are, at the tiny cell's sizes."""
    traffic = _committed(mix)
    small = dict(traffic, **{k: C.TINY_VIT_MIX[k] for k in (
        "n_clients", "participation", "local_steps", "batch_size",
        "partition", "data")})
    cell = dataclasses.replace(vit_cell, traffic=small)
    ref, binding = _family(cell)
    exp = harness.build(cell, C.TEST_SEED, ref, binding)
    assert math.isfinite(exp.run_round()["loss"])
