"""How a ``vit`` configuration runs through the system's normal path.

The task is the registered ``synth_image`` source (class-dependent Gaussian
images, partitioned over clients) with a vision backbone registered through
the public ``repro.scenarios.vision.register_vision_model`` hook.  The
backbone's weights are the benchmark's own (``references/vit.py``, made on the
device from the run's seed); its forward pass is the system's ``vit_apply``.
The images come from the mix's fixed data seed, as a data set would.
"""
from __future__ import annotations

from repro.api import PartitionSpec, ScenarioSpec
from repro.models.vision import vit_apply
from repro.scenarios.vision import register_vision_model

MODEL = "chipbench_vit"
# the configuration key that states the parameters' dtype
DTYPE_KEY = "param_dtype"

# what the system's ViT computes and cannot be told otherwise; a
# configuration that states anything else is not one this system runs
SYSTEM_FIXED = {"num_channels": 3, "layer_norm_eps": 1e-6, "qkv_bias": False,
                "attn_out_bias": False, "hidden_act": "gelu_tanh"}


def check_runnable(cfg: dict) -> None:
    wrong = {k: (cfg.get(k), v) for k, v in SYSTEM_FIXED.items()
             if cfg.get(k) != v}
    if wrong:
        raise ValueError(f"the system's ViT cannot run {wrong} "
                         "(stated, fixed by the system)")


def scenario(cfg: dict, traffic: dict, ref, dtype,
             seed: int) -> ScenarioSpec:
    check_runnable(cfg)
    meta = {"patch": cfg["patch_size"], "heads": cfg["num_attention_heads"]}

    def factory(_data_seed, *, image_size, n_classes):
        if (image_size, n_classes) != (cfg["image_size"], cfg["num_labels"]):
            raise ValueError("scenario and configuration disagree on the "
                             "image size or the number of classes")
        params = ref.init_params(cfg, seed, dtype)
        return params, lambda p, x: vit_apply(p, meta, x)

    register_vision_model(MODEL, factory)
    data, part = traffic["data"], traffic["partition"]
    return ScenarioSpec(
        name="chipbench", source="synth_image",
        partition=PartitionSpec(kind=part["kind"], alpha=part["alpha"],
                                min_size=part["min_size"]),
        model=MODEL, n_clients=traffic["n_clients"],
        batch_size=traffic["batch_size"],
        source_kwargs={"n": data["n_train"], "n_eval": data["n_eval"],
                       "noise": data["noise"],
                       "image_size": cfg["image_size"],
                       "n_classes": cfg["num_labels"]})
