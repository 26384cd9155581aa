"""A whole run of a tiny dense cell (limits of its own) comes out
correct, and comes out not correct with the timed path broken."""
import pytest

import chipbench_testlib as lib


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("state_unchanged", False), ("half_batch", False)])
def test_dense_cell_check(root, fault, correct):
    out = lib.run_cell(root, "tiny_dense.t", fault)
    assert out["correct"] is correct, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "check"
