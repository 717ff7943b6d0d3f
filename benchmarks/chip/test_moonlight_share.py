"""The Moonlight-16B-A3B share cell's own pieces on the CPU: its model FLOPs
against a hand count, the grouped GEMM roofline reader, and its whole
harness path at reduced widths (the held share, the sigmoid router, the
dense prefix), where a sound run is correct and the bfloat16 control is
not."""
import pytest

import harness
import small
import trace_reduce as tr

CELL = "moonlight-16b-a3b-share.k1-b4-s2048-i4"
# every width scaled down; 8 routed experts, 2 held (experts 2 and 3), 3
# per token
SMALL = {"hidden_size": 64, "intermediate_size": 96,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
         "v_head_dim": 16, "moe_intermediate_size": 32,
         "num_hidden_layers": 3, "n_routed_experts": 2, "held_offset": 2,
         "num_experts_per_tok": 3, "vocab_size": 256,
         "reference_block_rows": 2,
         "published": {"num_hidden_layers": 27, "n_routed_experts": 8,
                       "vocab_size": 163840}}


def _cell():
    full = harness.Cell(CELL)
    return harness.Cell(CELL, cfg=dict(full.cfg, **SMALL),
                        traffic=dict(full.traffic, **small.TRAFFIC))


def test_flops_hand_count():
    cell = _cell()
    d, S, H = 64, 16, 4
    # per position: q 64x4x24, kv_a 64x40, kv_b 32x4x32, o 64x64 (3 layers);
    # the dense MLP 3x64x96 (1 layer); per MoE layer (2) the router 64x8,
    # shared 3x64x64, held experts 3 of 8 chosen, 2 of 8 held: 3x2/8 of
    # 3x64x32
    mla = 64 * 96 + 64 * 40 + 32 * 128 + 64 * 64
    moe = 64 * 8 + 3 * 64 * 64 + 3 * 2 / 8 * 3 * 64 * 32
    per_pos = 3 * mla + 3 * 64 * 96 + 2 * moe
    attn = H * (24 + 16) * S * (S + 1) // 2       # scores and values
    assert cell.model.flops_per_sample(cell.cfg, cell.traffic) == \
        pytest.approx(6 * (S * per_pos + 3 * attn + d))


def test_full_size_flops():
    full = harness.Cell(CELL)
    # 1.817 GFLOP per token at 2048 positions (PERF.md)
    assert full.model.flops_per_sample(full.cfg, full.traffic) / 2048 == \
        pytest.approx(1.8171433e9, rel=1e-6)


def _reader(name):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read


def test_grouped_matmul_roofline_reader():
    full = harness.Cell(CELL)
    ops = {0: [tr.Op("grouped_matmul.3", 0, 2_000_000),
               tr.Op("grouped_matmul_wgrad.1", 2_000_000, 3_000_000),
               tr.Op("fusion.1", 3_000_000, 9_000_000)]}
    run = harness.RunInfo(
        cell=full, seconds=1.0, windows=2, samples=32, setup_s=1.0,
        peak_bytes=0, input_s=[], params_per_worker=0, flops_per_sample=0,
        peaks={"bf16_flops_per_s": 197e12}, trace=tr.Trace(ops=ops, spans=[]))
    # 2 windows of 4 steps; 5 MoE layers; 6,144 rows per layer and step
    flops = 9 * 2 * 6144 * 2048 * 1408 * 5 * 8
    assert _reader("grouped_matmul_roofline")(run) == pytest.approx(
        100 * flops / 197e12 / 3e-3)
    run.trace = tr.Trace(ops={0: ops[0][2:]}, spans=[])
    assert _reader("grouped_matmul_roofline")(run) is None


def test_sound_run_is_correct(monkeypatch):
    monkeypatch.setattr(harness, "peaks_for", small.v5e_peaks)
    result, _ = harness.run(_cell(), 2 ** 31 + 7, 0.2, False)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0


def test_control_fails():
    import calibrate

    cell = _cell()
    batches = calibrate.window_batches(cell, 2 ** 31 + 11)
    want = harness.reference(cell, 2 ** 31 + 11, batches)
    got = harness.reference(cell, 2 ** 31 + 11, batches, control=True)
    assert not harness.passed(harness.check_numbers(cell, got, want, 0))
