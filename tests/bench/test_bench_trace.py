"""The benchmark's trace reduction and work counts, on synthetic traces."""

import pytest

from bench import counts, peaks, trace as T


def _trace(ops, modules=(), spans=(), window=(0, 1000)):
    return T.Trace(window, [list(ops)], [list(modules)],
                   [(window[0], window[1], T.WINDOW_SPAN)] + list(spans))


def test_idle_share_is_one_minus_the_union_of_op_intervals():
    # overlapping ops count once; ops outside the window are clipped
    tr = _trace([(100, 300, "a"), (200, 400, "b"), (900, 1200, "c"),
                 (-50, 50, "d")])
    assert T.busy_s(tr) == pytest.approx((300 + 100 + 50) * 1e-9)
    assert T.idle_share(tr) == pytest.approx(1 - 450 / 1000)


def test_busy_is_averaged_over_devices():
    tr = T.Trace((0, 100), [[(0, 100, "x")], [(0, 50, "x")]], [[], []],
                 [(0, 100, T.WINDOW_SPAN)])
    assert T.busy_s(tr) == pytest.approx(75e-9)


def test_program_calls_match_jit_module_names_only():
    mods = [(0, 10, "jit_decode_step(12)"), (20, 50, "jit_decode_step"),
            (60, 70, "jit_decode_step_other(3)"),
            (80, 90, "jit_prefill_chunk_step(4)")]
    tr = _trace([], mods)
    assert T.program_calls(tr, "decode_step") == pytest.approx(
        [10e-9, 30e-9])
    assert T.program_calls(tr, "prefill_chunk_step") == pytest.approx(
        [10e-9])


def test_top_ops_and_idle_gaps_named_by_host_span():
    ops = [(0, 100, "fusion"), (150, 200, "fusion"), (200, 260, "dot"),
           (600, 1000, "dot")]
    spans = [(100, 160, "bench.pool_step"), (260, 600, "bench.idle_wait"),
             (250, 300, "bench.submit")]
    tr = _trace(ops, spans=spans)
    top = T.top_ops(tr)
    assert top[0][0] == "dot" and top[0][1] == pytest.approx(460e-9)
    gaps = T.idle_gaps(tr)
    assert gaps[0] == ["bench.idle_wait", pytest.approx(340e-9)]
    assert gaps[1] == ["bench.pool_step", pytest.approx(50e-9)]


@pytest.mark.parametrize("conf,weights", [
    # qwen3-14b at 4 layers: 4 x (2*5120*5120 + 2*5120*1024 + 3*5120*17408)
    # + 5120 * 152064
    ({"hidden_size": 5120, "num_attention_heads": 40,
      "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 17408,
      "num_hidden_layers": 4, "vocab_size": 152064, "dtype": "bfloat16"},
     4 * (2 * 5120 * 5120 + 2 * 5120 * 1024 + 3 * 5120 * 17408)
     + 5120 * 152064),
    # mistral-nemo-12b at 4 layers: q/o are 5120 x 4096
    ({"hidden_size": 5120, "num_attention_heads": 32,
      "num_key_value_heads": 8, "head_dim": 128, "intermediate_size": 14336,
      "num_hidden_layers": 4, "vocab_size": 131072, "dtype": "bfloat16"},
     4 * (2 * 5120 * 4096 + 2 * 5120 * 1024 + 3 * 5120 * 14336)
     + 5120 * 131072),
])
def test_dense_weight_counts(conf, weights):
    assert counts.dense_weights(conf) == weights
    # one cached position: K and V of every KV head in every layer, bf16
    assert counts.kv_bytes(conf, 1) == 2 * 8 * 128 * 2 * 4


def _metric(name):
    from bench.harness import metric_reader
    return metric_reader(name)


def test_mfu_train_counts_six_flops_per_weight_per_token_plus_attention():
    conf = {"hidden_size": 5120, "num_attention_heads": 32,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 14336, "num_hidden_layers": 4,
            "vocab_size": 131072, "dtype": "bfloat16"}
    tr = _trace([], [(0, 500_000_000, "jit_train_step(1)")],
                window=(0, 1_000_000_000))
    obs = {"trace": tr, "conf": conf, "mix": {"batch": 4, "seq_len": 2048},
           "peaks": peaks.peaks_for("TPU v5 lite"), "counters": {}}
    # LFA needs no dense weight gradient: 4 (not 6) per weight per token
    flops = (4 * counts.dense_weights(conf) * 8192
             + 3 * 4 * 32 * 128 * 4 * (4 * 2048 * 2049 // 2))
    assert _metric("mfu.train")(obs) == pytest.approx(
        100 * flops / 0.5 / 197e12)
    six = flops + 2 * counts.dense_weights(conf) * 8192
    assert _metric("mfu.train")(obs) < 100 * six / 0.5 / 197e12


def test_mfu_train_hand_count_at_the_tiny_size():
    # 2 layers of d 64, 4 heads x 16, 2 KV heads, d_ff 128, vocab 512:
    # q, o 64 x 64; k, v 64 x 32; gate, up, down 64 x 128; head 64 x 512
    conf = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "head_dim": 16,
            "intermediate_size": 128, "num_hidden_layers": 2,
            "vocab_size": 512, "dtype": "float32"}
    weights = 2 * (2 * 4096 + 2 * 2048 + 3 * 8192) + 32768
    assert counts.dense_weights(conf) == weights == 106496
    tokens, pairs = 2 * 32, 2 * (32 * 33 // 2)
    flops = 4 * weights * tokens + 3 * (4 * 4 * 16 * pairs * 2)
    assert flops == 27_262_976 + 1_622_016
    tr = _trace([], [(0, 1_000_000, "jit_train_step(3)"),
                     (2_000_000, 3_000_000, "jit_train_step(3)")],
                window=(0, 4_000_000))
    obs = {"trace": tr, "conf": conf, "mix": {"batch": 2, "seq_len": 32},
           "peaks": {"bf16_flops": 1e12}, "counters": {}}
    assert _metric("mfu.train")(obs) == pytest.approx(
        100 * flops / 1e-3 / 1e12)


def test_mfu_decode_takes_the_binding_bound():
    conf = {"hidden_size": 5120, "num_attention_heads": 40,
            "num_key_value_heads": 8, "head_dim": 128,
            "intermediate_size": 17408, "num_hidden_layers": 4,
            "vocab_size": 152064, "dtype": "bfloat16"}
    tr = _trace([], [(0, 10_000_000, "jit_decode_step(7)")],
                window=(0, 20_000_000))
    steps = [(0.0, 128, 128 * 800), (0.1, 128, 128 * 800)]
    obs = {"trace": tr, "conf": conf, "counters": {"traced_steps": steps},
           "peaks": peaks.peaks_for("TPU v5 lite"), "core_params": 40_000_000}
    flops = 2 * counts.dense_weights(conf) * 128 + 4 * 40 * 128 * 128 * 800 * 4
    nbytes = 40_000_000 * 2 + counts.kv_bytes(conf, 128 * 800)
    least = max(flops / 197e12, nbytes / 819e9)
    assert _metric("mfu.decode")(obs) == pytest.approx(100 * least / 0.01)


def test_readers_find_nothing_without_a_trace():
    obs = {"trace": None, "counters": {}, "conf": {}, "mix": {}}
    for name in ("mfu.train", "mfu.decode", "prefill_chunk_ms.chat",
                 "decode_step_ms.batch", "idle_share.chat",
                 "queue_wait_p50_ms.chat", "slot_occupancy.batch"):
        assert _metric(name)(obs) is None


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")
