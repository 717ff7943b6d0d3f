"""The trace reduction: busy union, kernel time by name, exposed collective
time and idle gaps, on hand-made events with known overlaps and on a small
trace recorded on a TPU v5e chip."""
import pathlib

import pytest

import trace_reduce as tr

TESTDATA = pathlib.Path(__file__).resolve().parent / "testdata"


def _trace():
    # chip 0, in ns: fusion [0, 10], all-reduce [5, 20] (5..10 hidden under
    # the fusion), prox kernel [25, 30], conv [28, 40]; chip 1: all-reduce
    # [0, 8] alone, copy [10, 12]
    op = tr.Op
    return tr.Trace(
        ops={0: [op("fusion.1", 0, 10), op("all-reduce.3", 5, 20),
                 op("prox_update", 25, 30), op("convolution.2", 28, 40)],
             1: [op("all-reduce.3", 0, 8), op("copy.1", 10, 12)]},
        spans=[op("sample_window", 19, 26), op("window_step", 40, 50)])


def test_merge_and_length():
    assert tr.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert tr.length([(0, 10), (5, 20), (25, 30), (28, 40)]) == 35


def test_uncovered():
    assert tr.uncovered([(5, 20)], [(0, 10)]) == 10
    assert tr.uncovered([(0, 10)], [(2, 3), (5, 6)]) == 8
    assert tr.uncovered([(0, 10)], [(-5, 15)]) == 0
    assert tr.uncovered([(0, 4), (6, 10)], [(3, 7)]) == 6


def test_gaps():
    assert tr.gaps([(2, 3), (5, 6)], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert tr.gaps([(0, 10)], 0, 10) == []


def test_busy_kernel_and_exposed():
    t = _trace()
    assert tr.busy_ns(t, 0) == 35
    assert tr.busy_ns(t, 1) == 10
    assert tr.kernel_ns(t, 0, r"prox_update") == 5
    assert tr.kernel_ns(t, 0, r"all-reduce") == 15
    assert tr.exposed_ns(t, 0, r"all-reduce") == 10
    assert tr.exposed_ns(t, 1, r"all-reduce") == 8


def test_names():
    assert tr.short_name("%fusion.12 = f32[4]{0} fusion(f32[4]{0} %p)") == \
        "fusion.12"
    assert tr.base_name("fusion.12") == "fusion"
    assert tr.base_name("pad.86.clone") == "pad"
    assert tr.base_name("vmap_jvp_jit_auc_loss___") == \
        "vmap_jvp_jit_auc_loss___"


def test_nested_loop():
    """A loop's op spans its body's ops: it is busy time, its own time is
    what its body leaves uncovered, and it hides no collective."""
    op = tr.Op
    t = tr.Trace(ops={0: [op("fusion.1", 2, 4), op("while.6", 0, 10),
                          op("all-reduce.1", 6, 9), op("copy.2", 8, 12)]},
                 spans=[])
    loop = [o for o in t.ops[0] if o.name == "while.6"][0]
    assert not loop.leaf and loop.self_ns == 10 - 2 - 3
    assert tr.busy_ns(t, 0) == 12
    assert tr.exposed_ns(t, 0, r"all-reduce") == 2
    assert dict(tr.top_ops(t))["while"] == pytest.approx(5 / 1e9)


def test_top_ops_and_idle_gaps():
    t = _trace()
    top = dict(tr.top_ops(t))
    assert top["all-reduce"] == pytest.approx((15 + 8) / 2 / 1e9)
    # chip 0 idles in [20, 25] under sample_window and [40, 50] under
    # window_step
    assert tr.idle_gaps(t, 0) == [("window_step", 10 / 1e9),
                                  ("sample_window", 5 / 1e9)]


def test_recorded_chip_trace():
    """The first window of ``resnet50.k4-b32-i4`` recorded on one v5e chip
    (``from_profile`` of its ``.xplane.pb``, written compact by ``dump``):
    the reduction finds the chip, the scan loop, every prox_update launch
    and the host spans."""
    t = tr.load_dump(str(TESTDATA / "resnet50.k4-b32-i4.trace.json.gz"))
    assert t.chips() == [0]
    ops = t.ops[0]
    window = max(o.end for o in ops) - min(o.start for o in ops)
    busy = tr.busy_ns(t, 0)
    assert 0.9 * window < busy <= window
    # 4 local steps x 153 parameter leaves, one launch each
    assert sum(o.name.startswith("prox_update") for o in ops) == 4 * 153
    prox = tr.kernel_ns(t, 0, r"prox_update")
    assert 0 < prox < 0.1 * busy
    assert sum(o.self_ns for o in ops) == pytest.approx(busy, rel=1e-6)
    assert [o.name for o in ops if not o.leaf][:1] == ["while.6"]
    assert tr.exposed_ns(t, 0, r"all-reduce") == 0
    assert {s.name for s in t.spans} == {"sample_window", "window_step"}
    assert tr.idle_gaps(t, 0)


def test_recorded_four_chip_trace():
    """The first window of ``resnet50.4chip.k4-b32-i4`` on four v5e chips:
    each chip runs one worker's 612 prox_update launches and one blocking
    all-reduce (``psum``) of the 94 MB payload, during which nothing else
    runs."""
    import importlib.util

    t = tr.load_dump(str(TESTDATA / "resnet50.4chip.k4-b32-i4.trace.json.gz"))
    assert t.chips() == [0, 1, 2, 3]
    spec = importlib.util.spec_from_file_location(
        "allreduce_reader", TESTDATA.parent / "metrics"
        / "allreduce_exposed_ms_per_window.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    for c in t.chips():
        ops = t.ops[c]
        assert sum(o.name.startswith("prox_update") for o in ops) == 4 * 153
        assert [o.name for o in ops if o.name.startswith("psum")] == ["psum.7"]
        hit = tr.kernel_ns(t, c, reader.COLLECTIVE)
        exposed = tr.exposed_ns(t, c, reader.COLLECTIVE)
        assert 1.0e6 < exposed <= hit
        assert exposed > 0.9 * hit
