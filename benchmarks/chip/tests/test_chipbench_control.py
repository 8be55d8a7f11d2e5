"""The int8 control, at a test's size: the reference with every matrix
product in int8 stands in for the program, and on three seeds it fails
the mean logit gap that the program's own served tokens pass, by the
harness's own verdict."""
import pytest

import cells


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return cells.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_int8_control_is_not_correct(root, seed):
    res = cells.cpu_run(root, "tiny-chat", seed, control=True)
    chk, ctl = res["check"], res["control"]
    assert res["correct"], chk
    assert ctl["correct"] is False, ctl
    assert ctl["mean_logit_gap"] > chk["mean_logit_gap"]["limit"], ctl
