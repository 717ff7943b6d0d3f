"""A cell's whole harness path on the CPU at reduced sizes, with the Pallas
kernels in interpret mode: set-up, the timed window, the reference and the
check, and the result line a run prints."""
import json

import pytest

import harness
import small


@pytest.fixture
def v5e(monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", small.v5e_peaks)


@pytest.mark.parametrize("trace", [False, True])
def test_result_line(v5e, trace):
    cell = small.cell("resnet50.k4-b32-i4", impl="pallas")
    result, lines = harness.run(cell, 2 ** 31 + 5, 0.3, trace)
    line = json.loads(json.dumps(result))
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    if trace:
        keys.append("breakdown")
    assert list(line) == keys + ["checks"]
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert line["device"]["count"] >= 1
    want = {m["name"] for m in cell.metrics(trace)}
    assert set(line["metrics"]) <= want
    if not trace:
        assert set(line["metrics"]) == want
    for m in line["metrics"].values():
        assert m["value"] == m["value"] and m["unit"]
    assert set(line["checks"]) == set(cell.limits) | {"window_compiles"}
    assert line["checks"]["window_compiles"]["value"] == 0
    assert lines[0].startswith("setup seconds:")
    assert lines[1].startswith("peak GiB: the program's state made")
    assert lines[2].startswith(f"timed windows: {line['attempted']},")
    assert [l.split(":")[0] for l in lines[3:]] == \
        [f"check {k}" for k in line["checks"]]


def test_same_seed_same_batches():
    import generator

    cell = small.cell("stablelm2-1.6b-share.k1-b16-s512-i4")
    seed = 2 ** 33 + 1
    draws = []
    for _ in range(2):
        ds = generator.Dataset(seed, cell.model.inputs(cell.cfg),
                               cell.traffic, cell.K)
        s = generator.Sampler(ds, cell.B, seed, None)
        draws.append([s.host_window(cell.I) for _ in range(2)])
    for a, b in zip(*draws):
        for k in a:
            assert (a[k] == b[k]).all()
    assert draws[0][0]["tokens"].shape == (cell.I, cell.K, cell.B,
                                          cell.traffic["seq_len"])


def test_only_iid_shards():
    import generator

    cell = small.cell("resnet50.k4-b32-i4")
    inputs = cell.model.inputs(cell.cfg)
    generator.Dataset(3, inputs, dict(cell.traffic, shards="iid"), cell.K)
    with pytest.raises(ValueError, match="shards"):
        generator.Dataset(3, inputs, dict(cell.traffic, shards="dirichlet"),
                          cell.K)
