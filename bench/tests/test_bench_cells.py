"""Each cell's task functions, through ``Wilkins.run`` at a tiny size on the
CPU, match the plain reference, and every metric of the cell is read."""

import pytest

from bench_tiny import cells, copy_bench, harness, run


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return copy_bench(str(tmp_path_factory.mktemp("tiny")))


@pytest.mark.parametrize("cell", cells())
@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "traced"])
def test_cell_matches_reference(tiny, cell, trace):
    res = run(tiny, cell, seed=2**31 + 17, trace=trace)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(c["value"] == 0 and c["limit"] == 0
               for c in res["checks"].values())
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in harness.metrics_for(
        harness.load_benchmark(), cell, trace)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    if trace:
        assert 0 < res["device"]["busy_s"] < res["device"]["window_s"]
        assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
        assert len(res["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("config", ["nyx_reeber_512", "synthetic_48x16"])
def test_same_seed_same_inputs(tiny, config):
    bench_dir, _ = tiny
    cfg = harness.load_json(bench_dir, "configs", config)
    module = harness.load_module(bench_dir, "configs", cfg["module"])

    def first(seed):
        r = harness.Run(seed=seed, cfg=cfg, traffic={}, seconds=0)
        return module.reference(r, [(0, 0), (1, 2)])

    big = 2**33 + 5   # more than 32 bits: the high word changes the data
    assert first(5) == first(5)
    assert first(5) != first(6)
    assert first(big) != first(5) and first(big) == first(big)
