"""``correct`` on the tiny lm cell: a sound run passes, a run with its
timed path broken fails, and the bfloat16 control fails."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chipbench_cells as C  # noqa: E402
from chipbench.faults import FAULTS  # noqa: E402

CELL = "tiny_lm.d"


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return C.make_tree(str(tmp_path_factory.mktemp("bench")),
                       limits=C.TEST_LIMITS)


def test_a_sound_run_is_correct(tree):
    res = C.run_tiny(tree, CELL)
    assert res["correct"], res["checks"]
    assert list(res)[:3] == ["correct", "attempted", "failed"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s", "peak_hbm_gib", "setup_s"}
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(res["device"])
    for check in res["checks"].values():
        assert set(check) == {"value", "limit"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_timed_path_is_not_correct(tree, fault):
    res = C.run_tiny(tree, CELL, fault=fault)
    assert not res["correct"], res["checks"]


def test_the_bfloat16_control_is_not_correct(tmp_path):
    # the configuration states bfloat16 parameters: the program's own
    # lower-precision path, held to the float32 reference
    tree = C.make_tree(str(tmp_path), limits=C.TEST_LIMITS,
                       dtype="bfloat16")
    res = C.run_tiny(tree, CELL)
    assert not res["correct"], res["checks"]
