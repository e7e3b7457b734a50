"""``correct`` on the tiny FedAvg cell: the system's ``fedavg`` rounds agree
with ``references/fedavg.py``; a run with its timed path broken fails, and
so does the bfloat16 control."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chipbench_cells as C  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402

CELL = "tiny_vit.avg"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return C.make_tree(str(tmp_path_factory.mktemp("bench")),
                       limits=C.FEDAVG_TEST_LIMITS)


def test_a_sound_run_is_correct(tree):
    res = C.run_tiny(tree, CELL)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    # FedAvg keeps no Theta: nothing to compare there
    assert set(res["checks"]) == set(C.FEDAVG_TEST_LIMITS)


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tree, fault):
    res = C.run_tiny(tree, CELL, fault=fault)
    assert not res["correct"], res["checks"]


def test_the_bfloat16_control_is_not_correct(tmp_path):
    tree = C.make_tree(str(tmp_path), limits=C.FEDAVG_TEST_LIMITS,
                       dtype="bfloat16")
    res = C.run_tiny(tree, CELL)
    assert not res["correct"], res["checks"]
