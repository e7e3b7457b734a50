"""The command fails, and prints no result, where it cannot measure."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))


def _run(cwd, workload="vit_s16.c8_k10_qblock"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", workload,
         "--seed", str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _run_module():
    """``run.py`` by path: another ``run`` module may be imported first."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_run_main", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except json.JSONDecodeError:
            pass
    return False


@pytest.mark.parametrize("workload", ["vit_s16.c8_k10_qblock",
                                      "smollm360m.c4_seq1024"])
def test_refuses_a_host_without_a_tpu(workload):
    proc = _run(REPO, workload)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "TPU" in proc.stderr and "cpu" in proc.stderr


def test_fails_in_a_tree_that_holds_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(str(tmp_path))
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)


def test_unknown_workload_fails():
    proc = _run(REPO, "no_such_cell")
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)


@pytest.mark.parametrize("extra", [["--precision", "high"],
                                   ["--fault", "half_batch"],
                                   ["--fault", "state_unchanged"]])
def test_control_options_parse(extra):
    args = _run_module().parse_args(["--workload", "w", "--seed", str(2 ** 33 + 1),
                           "--seconds", "1"] + extra)
    assert args.seed == 2 ** 33 + 1 and args.trace == 0
    assert (args.precision, args.fault) != (None, None)


@pytest.mark.parametrize("extra", [["--precision", "bf16"],
                                   ["--fault", "no_such_fault"]])
def test_unknown_control_options_are_refused(extra):
    with pytest.raises(SystemExit):
        _run_module().parse_args(["--workload", "w", "--seed", "1", "--seconds", "1"]
                       + extra)
