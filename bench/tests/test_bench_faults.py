"""With the timed path broken underneath, ``correct`` comes out false: the
control (a bfloat16 transport) and each fault a cell can have."""

import pytest

from bench_tiny import cells, copy_bench, faults, run


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return copy_bench(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_fault_makes_run_incorrect(tiny, cell, fault):
    res = run(tiny, cell, seconds=0.2, fault=fault)
    assert not res["correct"]
    assert res["failed"] > 0
    assert sum(c["value"] for c in res["checks"].values()) > 0


def test_faults_are_removed_after_the_run(tiny):
    cell = cells()[0]
    assert not run(tiny, cell, seconds=0.2, fault="altered")["correct"]
    assert run(tiny, cell, seconds=0.2)["correct"]
