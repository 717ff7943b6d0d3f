"""Model FLOPs of both configurations and the proximal update's byte count,
against hand counts at small sizes; the configurations' weights against the
program's parameter layout."""
import types

import jax
import jax.numpy as jnp
import pytest

import harness

RESNET_SMALL = {"stages": [[1, 64], [1, 128]],
                "stem": {"kernel": 3, "stride": 1, "channels": 16},
                "image_hw": 8}
LM_SMALL = {"hidden_size": 64, "intermediate_size": 128,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "num_hidden_layers": 2, "vocab_size": 256}


def _model(name, small):
    mod = harness.load_module(harness.HERE / "configs" / f"{name}.py")
    cfg = harness.load_json(harness.HERE / "configs" / f"{name}.json")
    return mod, dict(cfg, **small)


def test_resnet_flops_hand_count():
    mod, cfg = _model("resnet50-cifar32", RESNET_SMALL)
    # multiply-adds: stem 8*8*3*3*3*16; stage 0 (8x8, 16 -> 64, mid 16):
    # 1x1, 3x3, 1x1, projection; stage 1 (stride 2 to 4x4, 64 -> 128,
    # mid 32): 1x1 at 8x8, then 3x3, 1x1, projection at 4x4; head 128
    macs = (64 * 27 * 16
            + 64 * 16 * 16 + 64 * 9 * 16 * 16 + 64 * 16 * 64 + 64 * 16 * 64
            + 64 * 64 * 32 + 16 * 9 * 32 * 32 + 16 * 32 * 128
            + 16 * 64 * 128
            + 128)
    assert mod.flops_per_sample(cfg, {}) == 6 * macs


def test_resnet50_full_flops():
    mod = harness.load_module(harness.HERE / "configs" / "resnet50-cifar32.py")
    cfg = harness.load_json(harness.HERE / "configs" / "resnet50-cifar32.json")
    assert mod.flops_per_sample(cfg, {}) == pytest.approx(7.787e9, rel=1e-3)


def test_stablelm_flops_hand_count():
    mod, cfg = _model("stablelm2-1.6b-share", LM_SMALL)
    S, d, ff = 16, 64, 128
    proj = S * (4 * d * d + 3 * d * ff)     # q, k, v, o and the SwiGLU MLP
    attn = 2 * d * (S * (S + 1) // 2)       # causal scores and values
    assert mod.flops_per_sample(cfg, {"seq_len": S}) == \
        6 * (2 * (proj + attn) + d)


@pytest.mark.parametrize("name,small", [("resnet50-cifar32", RESNET_SMALL),
                                        ("stablelm2-1.6b-share", LM_SMALL)])
def test_weights_have_program_layout(name, small):
    from repro.models import model as M

    mod, cfg = _model(name, small)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    mine = jax.eval_shape(lambda k: mod.make_weights(k, cfg), key)
    theirs = jax.eval_shape(
        lambda k: M.init_params(k, mod.model_config(cfg)), key)
    assert jax.tree_util.tree_structure(mine) == \
        jax.tree_util.tree_structure(theirs)
    assert [(l.shape, l.dtype) for l in jax.tree_util.tree_leaves(mine)] == \
        [(l.shape, l.dtype) for l in jax.tree_util.tree_leaves(theirs)]


def _run(**kw):
    cell = types.SimpleNamespace(K=4, I=2, chips=1, traffic={"seq_len": 8})
    base = dict(cell=cell, seconds=1.0, windows=4, samples=1000,
                setup_s=1.0, peak_bytes=2 ** 30, input_s=[0.002, 0.004],
                params_per_worker=1000, flops_per_sample=1e9,
                peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                trace=None)
    base.update(kw)
    return harness.RunInfo(**base)


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read


def test_prox_update_bytes_and_roofline():
    import trace_reduce as tr

    trace = tr.Trace(ops={0: [tr.Op("prox_update", 0, 1_000_000)]}, spans=[])
    run = _run(trace=trace)
    # v, g, v0 read and v written, 4 bytes each, for 1000 parameters of 4
    # workers over 4 windows of 2 steps
    need = 16 * 1000 * 4 * 8
    assert _reader("prox_update_roofline")(run) == pytest.approx(
        100 * need / 819e9 / 1e-3)
    assert _reader("prox_update_roofline")(_run()) is None


def test_mfu_and_host_metrics():
    run = _run()
    assert _reader("mfu")(run) == pytest.approx(100 * 1e9 * 1000 / 197e12)
    assert _reader("samples_per_s")(run) == 1000
    assert _reader("tokens_per_s")(run) == 8000
    assert _reader("peak_hbm_gib")(run) == 1.0
    assert _reader("input_ms_per_window")(run) == pytest.approx(3.0)
    assert _reader("device_idle_frac")(run) is None
    assert _reader("window_ms_p50")(run) is None
    assert _reader("window_ms_p50")(_run(window_s=[0.3, 0.1, 0.2])) == \
        pytest.approx(200.0)


@pytest.mark.parametrize("name", ["mfu", "input_ms_per_window",
                                  "device_idle_frac", "peak_hbm_gib"])
def test_lm_readers_are_the_shared_ones(name):
    import trace_reduce as tr

    trace = tr.Trace(ops={0: [tr.Op("fusion.1", 0, 600_000_000)]}, spans=[])
    for run in (_run(), _run(trace=trace)):
        assert _reader(f"{name}.lm")(run) == _reader(name)(run)


def test_each_cell_reports_what_its_layers_move():
    manifest = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for w in manifest["workloads"]:
        cell = harness.Cell(w["name"])
        e2e = {m["name"] for m in cell.metrics(False)}
        layers = cell.metrics(True)
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert layers and all(m["moves"] in e2e for m in layers), w["name"]


def test_allreduce_exposed_reader():
    import trace_reduce as tr

    op = tr.Op
    # chip 0: an async all-reduce pair whose wait (done) is half hidden
    # under a fusion; chip 1: a plain all-reduce, alone
    trace = tr.Trace(ops={
        0: [op("all-reduce-start.1", 0, 1e6), op("fusion.2", 1e6, 3e6),
            op("all-reduce-done.1", 2e6, 5e6)],
        1: [op("all-reduce.1", 0, 2e6), op("fusion.2", 2e6, 3e6)]},
        spans=[])
    # exposed: chip 0, 1 ms (start) + 2 ms (done after the fusion); chip 1,
    # 2 ms; the most, over 4 windows
    assert _reader("allreduce_exposed_ms_per_window")(_run(trace=trace)) == \
        pytest.approx(3.0 / 4)
    assert _reader("allreduce_exposed_ms_per_window")(_run()) is None
