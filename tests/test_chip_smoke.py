"""chip_smoke.py off the chip: its phases at --reduced size on the CPU, its
refusal to run anywhere but a TPU, and the compile-cache helper."""
import math
import os
import sys

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.utils import hw

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402


@pytest.fixture
def private_compile_cache(monkeypatch, tmp_path):
    """Entry points turn the persistent compile cache on for the whole
    process; point it at a temporary directory and turn it off again."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    yield tmp_path / "cache"
    jax.config.update("jax_compilation_cache_dir", before)
    compilation_cache.reset_cache()


def _assert_finite(hist, rounds):
    assert [r["round"] for r in hist] == list(range(1, rounds + 1))
    for r in hist:
        assert math.isfinite(r["loss"]) and math.isfinite(r["eval_loss"])
        assert r["round_s"] > 0


def test_dense_phase_reduced(private_compile_cache):
    hist = chip_smoke.phase_dense(reduced=True, rounds=2)
    _assert_finite(hist, 2)
    assert jax.config.jax_compilation_cache_dir == str(private_compile_cache)


def test_qblock_phase_reduced(private_compile_cache):
    hist, kernels = chip_smoke.phase_qblock(reduced=True, rounds=2)
    _assert_finite(hist, 2)
    # off the chip the Pallas paths interpret and nothing lowers to a kernel
    assert kernels == {"interpret": True, "tpu_custom_call": 0}


def test_cohort_mesh_phase_reduced(private_compile_cache):
    hists = chip_smoke.phase_cohort_mesh(reduced=True, rounds=2)
    assert set(hists) == set(chip_smoke.MESH_EXECUTORS)
    for hist in hists.values():
        _assert_finite(hist, 2)
    chip_smoke.compare_executors(hists)   # one CPU device: the mesh is 1


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_main_refuses_cpu(argv, capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main(argv)
    assert exc.value.code not in (0, None)
    assert "'cpu'" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_compile_cache_dir_from_env(private_compile_cache):
    assert hw.enable_compile_cache() == str(private_compile_cache)
    assert jax.config.jax_compilation_cache_dir == str(private_compile_cache)


def test_compile_cache_dir_default_is_fixed(private_compile_cache,
                                            monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    first, second = hw.enable_compile_cache(), hw.enable_compile_cache()
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == second == os.path.join(root, ".jax_cache")
