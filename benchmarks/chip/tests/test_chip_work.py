"""The benchmark's work arithmetic against the program's MAC accounting
and a hand count at qwen2.5-3b's published widths."""
import json
import os

import pytest

import work

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _qwen():
    with open(os.path.join(CHIP, "configs", "qwen2.5-3b.json")) as f:
        return work.sizes(json.load(f))


def test_token_macs_match_the_program_at_a_reduced_config():
    from repro.configs import get_config
    from repro.configs.base import reduced
    from repro.core.macs import segment_macs_per_token
    cfg = reduced(get_config("qwen2.5-3b"), n_layers=6)
    cfg = cfg.with_cascade(n_components=3, exit_boundaries=(2, 4),
                           thresholds=(0.5, 0.5, 0.0))
    s = {"d": cfg.d_model, "n_layers": cfg.n_layers,
         "n_heads": cfg.n_heads, "n_kv": cfg.n_kv_heads,
         "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
         "vocab": cfg.vocab_size, "bias": cfg.qkv_bias,
         "tied": cfg.tie_embeddings, "n_components": 3, "boundaries": [2, 4]}
    for kv_len in (1, 17, 300):
        prog = segment_macs_per_token(cfg, kv_len)
        for e in range(3):
            assert work.token_macs(s, e, kv_len) == prog[e]


def test_parameter_count_at_qwen_widths():
    s = _qwen()
    # 36 layers of 77,076,992, one tied embedding of 311,164,928, three
    # norm gains of 2,048: the published 3.09B
    assert s["tied"]
    assert work.layer_params(s) == 77_076_992
    assert work.param_count(s) == 3_085_942_784
    # untied, as the program's own qwen2.5-3b config builds it
    assert work.param_count(dict(s, tied=False)) == 3_397_107_712
    # a prefill reads the unembedding once whether or not it is tied
    assert work.prefill_bytes(s, 8, 512) == work.prefill_bytes(
        dict(s, tied=False), 8, 512)


def test_decode_bytes_and_flops_at_the_two_corners():
    s = _qwen()
    rows, ctx = 8, 600
    kv_row_layer = ctx * 2 * 256 * 2          # K and V, 2 kv heads x 128
    write_row = 36 * 2 * 256 * 2 + 2048 * 2    # one slot per layer + embed
    # no exit: every layer, the unembedding once, three norm gains
    noexit = (2 * (36 * 77_076_992 + 2048 * 151_936 + 3 * 2048)
              + rows * (36 * kv_row_layer + write_row))
    assert work.decode_step_bytes(s, [2] * rows, [ctx] * rows) == noexit
    # all exit at component 0: 12 layers, the unembedding, one norm gain,
    # and the K/V projections (with biases and attention norm) of the 24
    # backfilled layers
    backfill = 24 * (2 * 2048 * 256 + 2 * 256 + 2048)
    allexit = (2 * (12 * 77_076_992 + 2048 * 151_936 + 2048 + backfill)
               + rows * (12 * kv_row_layer + write_row))
    assert work.decode_step_bytes(s, [0] * rows, [ctx] * rows) == allexit
    layer_macs = (2048 * 2048 * 2 + 2 * 2048 * 256 + 16 * 128 * ctx * 2
                  + 3 * 2048 * 11008)
    head = 2048 * 151_936
    assert work.decode_row_flops(s, 2, ctx) == 2 * (36 * layer_macs
                                                    + 3 * head)
    assert work.decode_row_flops(s, 0, ctx) == 2 * (
        12 * layer_macs + head + 24 * 2 * 2048 * 256)


def test_prefill_flops_count_causal_pairs():
    s = _qwen()
    S = 512
    proj = 2048 * 2048 * 2 + 2 * 2048 * 256 + 3 * 2048 * 11008
    per_row = 36 * (S * proj + 2 * 16 * 128 * S * (S + 1) // 2) \
        + 3 * 2048 * 151_936
    assert work.prefill_flops(s, 8, S) == 2 * 8 * per_row


def test_least_seconds_is_the_larger_bound():
    peaks = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds(1000, 50, peaks) == pytest.approx(10.0)
    assert work.least_seconds(100, 50, peaks) == pytest.approx(5.0)


def test_metric_readers_on_hand_built_readings():
    import harness
    s = _qwen()
    peaks = harness.peaks_for("TPU v5 lite")
    steps = [([0] * 8, [600] * 8), ([0] * 8, [601] * 8),
             ([0, 1] * 4, [602] * 8), ([2] + [0] * 7, [603] * 8)]
    r = harness.Readings(
        window_s=10.0, prefills=[(0.2, 8, 512), (0.3, 8, 512)],
        chunks=[(0.05, 8, 100.0), (0.03, 4, 100.1)],
        lane_steps=steps, compiles=0, work=work, sizes=s, peaks=peaks,
        trace={"busy_s": 7.5, "window_s": 10.0})
    read = {n: m.read(r) for n, m in harness.load_metric_readers(
        ["prefill_ms", "compiles_in_window", "decode_step_ms",
         "deep_segment_runs_per_step", "decode_step_roofline", "mfu",
         "device_idle_share"]).items()}
    assert read["prefill_ms"] == pytest.approx(250.0)
    assert read["compiles_in_window"] == 0
    assert read["decode_step_ms"] == pytest.approx(80.0 / 12)
    # each lane step runs as many deep segments as its deepest row's exit
    assert read["deep_segment_runs_per_step"] == pytest.approx(3 / 4)
    least = sum(work.least_seconds(work.decode_step_flops(s, e, c),
                                   work.decode_step_bytes(s, e, c), peaks)
                for e, c in steps)
    assert read["decode_step_roofline"] == pytest.approx(100 * least / 0.08)
    flops = 2 * work.prefill_flops(s, 8, 512) + sum(
        work.decode_step_flops(s, e, c) for e, c in steps)
    assert read["mfu"] == pytest.approx(100 * flops / (10.0 * 197e12))
    assert read["device_idle_share"] == pytest.approx(25.0)
