"""How a ``llama`` configuration runs through the system's normal path.

The task is the registered ``lm_zipf`` source (topic-labelled Zipf documents,
partitioned over clients by topic) with a language model registered through
the public ``repro.scenarios.lm.register_lm_model`` hook.  The model is the
system's own architecture entry (``system_arch`` in the configuration) with
the sizes and dtype the configuration states; its weights are
the benchmark's own (``references/llama.py``, made on the device from the
run's seed).  The documents come from the mix's fixed data seed, as a corpus
would.
"""
from __future__ import annotations

import jax

from repro import configs
from repro.api import PartitionSpec, ScenarioSpec
from repro.models import model as M
from repro.scenarios.lm import register_lm_model

MODEL = "chipbench_llama"
# the configuration key that states the parameters' dtype
DTYPE_KEY = "torch_dtype"

# the system's RMSNorm epsilon is a constant of its code
SYSTEM_RMS_EPS = 1e-6


def system_config(cfg: dict, dtype: str):
    """The system's ModelConfig for ``cfg``: its own architecture entry with
    the sizes the configuration file states (the published ones, but for
    the depth)."""
    mc = configs.get_config(cfg["system_arch"]).replace(
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        rope_theta=cfg["rope_theta"], head_dim=0, dtype=dtype)
    fixed = {"mlp_type": "swiglu", "norm_type": "rms", "qkv_bias": False,
             "tie_embeddings": True, "block_pattern": ("attn",)}
    got = {k: getattr(mc, k) for k in fixed}
    if got != fixed or not cfg["tie_word_embeddings"] \
            or cfg["rms_norm_eps"] != SYSTEM_RMS_EPS:
        raise ValueError(f"system arch {cfg['system_arch']!r} has {got}; "
                         "the configuration needs a tied, bias-free "
                         f"SwiGLU/RMSNorm decoder with eps {SYSTEM_RMS_EPS}")
    return mc


def scenario(cfg: dict, traffic: dict, ref, dtype,
             seed: int) -> ScenarioSpec:
    mc = system_config(cfg, jax.numpy.dtype(dtype).name)
    expected = jax.tree.map(lambda s: s.shape, M.param_shapes(mc))

    def factory(_data_seed, *, vocab):
        if vocab != cfg["vocab_size"]:
            raise ValueError("scenario and configuration disagree on vocab")
        params = ref.init_params(cfg, seed, dtype)
        if jax.tree.map(lambda a: a.shape, params) != expected:
            raise ValueError("the reference's parameter layout is not the "
                             "system's")
        return params, mc

    register_lm_model(MODEL, factory)
    data, part = traffic["data"], traffic["partition"]
    return ScenarioSpec(
        name="chipbench", source="lm_zipf",
        partition=PartitionSpec(kind=part["kind"], alpha=part["alpha"],
                                min_size=part["min_size"]),
        model=MODEL, n_clients=traffic["n_clients"],
        batch_size=traffic["batch_size"],
        source_kwargs={"vocab": cfg["vocab_size"],
                       "seq_len": traffic["seq_len"],
                       "n_docs": data["n_docs"],
                       "tokens_per_doc": data["tokens_per_doc"],
                       "n_topics": data["n_topics"],
                       "n_eval_docs": data["n_eval_docs"],
                       "eval_batch": data["eval_batch"]})
