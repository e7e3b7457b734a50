"""Tiny cells for the CPU tests: a benchmark tree in a temporary directory.

``make_tree(root)`` writes ``BENCHMARK.json`` and a ``bench/`` directory with
one tiny ViT and one tiny Llama configuration, their mixes (FedPAC-SOAP on
both, FedAvg on the ViT too), their limits, and copies of the real
per-layer metric readers, so that ``spec.load_cell`` finds everything by
name there.  The families' references and bindings are the real ones.
"""
from __future__ import annotations

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for p in (BENCH, os.path.join(REPO, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SOAP = {"b1": 0.95, "b2": 0.95, "eps": 1e-8, "precond_freq": 10,
        "adam_b1": 0.9, "adam_b2": 0.999}

TINY_VIT = {
    "family": "vit", "source": "test", "hidden_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 2,
    "intermediate_size": 64, "image_size": 8, "patch_size": 4,
    "num_channels": 3, "num_labels": 4, "qkv_bias": False,
    "attn_out_bias": False, "hidden_act": "gelu_tanh",
    "layer_norm_eps": 1e-6, "param_dtype": "float32",
    "matmul_precision": "default", "reduced": []}

TINY_LM = {
    "family": "llama", "system_arch": "smollm-360m", "source": "test",
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 64,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "tie_word_embeddings": True,
    "initializer_range": 0.02, "torch_dtype": "float32",
    "matmul_precision": "default", "reduced": []}

TINY_VIT_MIX = {
    "algorithm": "fedpac_soap", "n_clients": 4, "participation": 0.5,
    "local_steps": 2, "batch_size": 16, "lr": 0.003, "beta": 0.5,
    "server_lr": 1.0, "soap": SOAP, "theta_codec": "qblock",
    "qblock_size": 128, "delta_codec": "dense", "executor": "vmap",
    "chunk_size": 8,
    "partition": {"kind": "dirichlet", "alpha": 0.5, "min_size": 2},
    "data": {"seed": 17, "n_train": 200, "n_eval": 16, "noise": 2.5},
    "check_rounds": 2}

TINY_LM_MIX = {
    "algorithm": "fedpac_soap", "n_clients": 4, "participation": 0.5,
    "local_steps": 2, "batch_size": 2, "seq_len": 16, "lr": 0.003,
    "beta": 0.5, "server_lr": 1.0, "soap": SOAP, "theta_codec": "dense",
    "qblock_size": 128, "delta_codec": "dense", "executor": "chunked",
    "chunk_size": 1,
    "partition": {"kind": "dirichlet", "alpha": 0.5, "min_size": 2},
    "data": {"seed": 17, "n_docs": 32, "tokens_per_doc": 64, "n_topics": 4,
             "n_eval_docs": 2, "eval_batch": 2}, "check_rounds": 2}

# FedAvg on the tiny ViT: local SGD at the paper's SGD rate, no Theta
TINY_FEDAVG_MIX = dict(
    {k: v for k, v in TINY_VIT_MIX.items() if k != "soap"},
    algorithm="fedavg", lr=0.1, beta=0.0, sgd={"momentum": 0.0},
    theta_codec="dense")

# float32 on the CPU against a float32 reference at the highest precision
LIMITS = {"loss": 1e-3, "grad": 1e-2, "theta": 1e-2, "delta": 1e-2}

CELLS = {"tiny_vit.q": ("tiny-vit", "tiny_q", TINY_VIT, TINY_VIT_MIX),
         "tiny_lm.d": ("tiny-lm", "tiny_d", TINY_LM, TINY_LM_MIX),
         "tiny_vit.avg": ("tiny-vit", "tiny_avg", TINY_VIT,
                          TINY_FEDAVG_MIX)}


def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


DTYPE_KEYS = {"vit": "param_dtype", "llama": "torch_dtype"}


def make_tree(root: str, limits=None, dtype: str = "float32") -> str:
    """Write a benchmark tree of the tiny cells under ``root``, their
    parameters in ``dtype``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = dict(real, paths=["bench"], configs=[], workloads=[])
    for name, (cfg_name, mix, cfg, traffic) in CELLS.items():
        bench["configs"].append({"name": cfg_name, "source": "test",
                                 "file": f"bench/configs/{cfg_name}.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": name, "config": cfg_name,
                                   "traffic": mix, "chips": 1,
                                   "why": "test"})
        cfg = dict(cfg, **{DTYPE_KEYS[cfg["family"]]: dtype})
        _dump(os.path.join(root, "bench", "configs", cfg_name + ".json"), cfg)
        _dump(os.path.join(root, "bench", "traffic", mix + ".json"), traffic)
        _dump(os.path.join(root, "bench", "limits", name + ".json"),
              {"limits": dict(limits or LIMITS)})
    # each real cell's metrics report in the tiny cell of its family
    tiny = {w["name"]: ("tiny_vit.q" if w["config"].startswith("vit")
                        else "tiny_lm.d") for w in real["workloads"]}
    for m in bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({tiny[w] for w in m["workloads"]})
    _dump(os.path.join(root, "BENCHMARK.json"), bench)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(root, "bench", "metrics"))
    return root


# tiny-size limits the sound CPU runs of the tests pass (largest readings
# on the test seed: loss 3.4e-4, grad 0.029, theta 0.0075, delta 0.018)
# and every planted fault and the bfloat16 control fail
TEST_LIMITS = {"loss": 5e-3, "grad": 0.08, "theta": 0.05, "delta": 0.08}
TEST_SEED = 5
# the same for the tiny FedAvg cell (largest sound readings on seeds 5-7:
# grad 3.9e-7, delta 9.1e-7; smallest of the bfloat16 control: grad 0.049,
# delta 0.092; of the half-batch fault: grad 0.36, delta 0.38)
FEDAVG_TEST_LIMITS = {"loss": 1e-4, "grad": 1e-3, "delta": 1e-3}


def run_tiny(root: str, cell_name: str, fault=None) -> dict:
    """One run of a tiny cell through the harness, past its chip check."""
    import time
    from chipbench import harness, spec
    cell = spec.load_cell(cell_name, repo_root=root)
    return harness.run_cell(cell, TEST_SEED, 0.5, False,
                            t_start=time.perf_counter(),
                            counter=harness.CompileEvents().install(),
                            fault=fault)
