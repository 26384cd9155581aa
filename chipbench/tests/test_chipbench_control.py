"""The control, the reference in float8 put in the program's place,
comes out not correct where the program comes out correct (tiny cells
on the CPU, with limits of their own)."""
import jax
import pytest

import chipbench_testlib as lib
from chipbench import bench, check


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return lib.make_root(str(tmp_path_factory.mktemp("bench")))


@pytest.mark.parametrize("cell", ["tiny_dense.t", "tiny_mamba.t"])
def test_control_fails_where_the_program_passes(root, cell):
    seed = 2**31 + 3
    b = bench.Bench(cell, jax.devices()[:1], root)
    state, loader, prog = b.start(seed)
    loader.close()
    bench.free(state)
    ref = b.reference(seed)
    ctrl = b.reference(seed, quant="fp8")
    limits = b.workload["limits"]
    sound, control = (check.compare(prog, ref, limits),
                      check.compare(ctrl, ref, limits))
    assert check.passed(sound), sound
    assert not check.passed(control), control
