"""Dry-run machinery on a small forced-device mesh (subprocess: jax locks the
device count at first init, so the 8-device test must run isolated)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro import configs
    from repro.launch import dryrun
    from repro.launch.specs import InputShape

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out = {}
    for arch, shape_name, kind in [
        ("smollm-360m", "train_4k", "train"),
        ("mixtral-8x22b", "decode_32k", "decode"),
        ("falcon-mamba-7b", "long_500k", "decode"),
        ("qwen2-vl-7b", "prefill_32k", "prefill"),
    ]:
        cfg = configs.get_reduced(arch)
        shape = InputShape(shape_name, 64, 8, kind)
        _, _, lowered = dryrun.build_lowering(
            arch, shape_name, mesh, cfg=cfg, shape_override=shape)
        compiled = lowered.compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, list):
            cost = cost[0]
        hlo = compiled.as_text()
        cb, per = dryrun.collective_bytes_from_hlo(hlo)
        out[f"{arch}:{shape_name}"] = {
            "flops": float(cost.get("flops", 0)),
            "collective_bytes": cb,
        }
    print("RESULT " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def dryrun_result():
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_all_small_combos_compile(dryrun_result):
    assert len(dryrun_result) == 4


def test_train_step_has_collectives(dryrun_result):
    # FSDP/TP sharding must produce cross-device traffic
    assert dryrun_result["smollm-360m:train_4k"]["collective_bytes"] > 0


def test_flops_positive(dryrun_result):
    for k, v in dryrun_result.items():
        assert v["flops"] > 0, k


def test_collective_parser():
    from repro.launch.dryrun import collective_bytes_from_hlo
    hlo = """
      %ag = bf16[2,64]{1,0} all-gather(%x), replica_groups={}
      %ar = f32[128]{0} all-reduce(%y), to_apply=%sum
      %noise = f32[4]{0} add(%a, %b)
    """
    total, per = collective_bytes_from_hlo(hlo)
    assert per["all-gather"] == 2 * 64 * 2
    assert per["all-reduce"] == 128 * 4
    assert total == per["all-gather"] + per["all-reduce"]
