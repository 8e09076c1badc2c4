"""The on-chip benchmark's arithmetic: tails over every request, tokens
inside the window, the byte and FLOP models against the program's own,
and weights made again one layer at a time."""
import jax
import numpy as np
import pytest

import bench_tiny as BT
from harness import report, roofline, runner, weights
from harness import traffic as T


def _rec(due, toks=(), source="cloud", done=None, in_window=True):
    r = runner.Record(T.Request(0, due, 0, np.zeros(10, np.int32), -1,
                                False))
    r.tok_s, r.source, r.in_window = list(toks), source, in_window
    r.done_s = done if done is not None else (toks[-1] if toks else due)
    return r


def _run(records, window=10.0):
    return runner.Run(records=records, window_s=window, steps_in_window=100,
                      setup={"setup_s": 1.0},
                      compiles_in_window=0, compile_names=[], late_s=[],
                      hot_tokens={}, trace_dir=None, memory_peak_bytes=0,
                      counters={})


def test_p95_is_over_every_request():
    # 100 requests: 95 at 10 ms, 5 at 1000 ms; a p95 over medians of
    # chunks would read 10
    recs = [_rec(0.0, done=0.01, source="edge") for _ in range(95)]
    recs += [_rec(0.0, done=1.0, source="peer") for _ in range(5)]
    got = report.hit_latencies_ms(_run(recs))
    assert len(got) == 100
    assert report.p95(got) == pytest.approx(np.percentile(got, 95))
    assert report.p95(got) > 10.0


def test_ttft_and_itl_are_taken_from_when_the_request_was_due():
    r = _rec(1.0, toks=[1.5, 1.6, 1.8])
    run = _run([r, _rec(2.0, source="edge", done=2.1)])
    assert report.ttft_ms(run) == [pytest.approx(500.0)]
    assert report.itl_ms(run, closed=False) == [pytest.approx(100.0),
                                                pytest.approx(200.0)]


def test_requests_due_after_the_window_are_left_out():
    run = _run([_rec(1.0, toks=[1.5]), _rec(11.0, toks=[11.5],
                                            in_window=False)])
    assert report.ttft_ms(run) == [pytest.approx(500.0)]


def test_tokens_counted_only_inside_the_window():
    recs = [_rec(-5.0, toks=[-1.0, 0.5, 9.9, 10.1], in_window=False),
            _rec(2.0, toks=[3.0, 4.0])]
    run = _run(recs)
    assert report.tokens_in_window(run) == 4
    assert report.end_to_end(run, closed=True)["out_tok_s"] == \
        pytest.approx(0.4)
    # closed loop: gaps with both tokens inside the window
    assert sorted(report.itl_ms(run, closed=True)) == \
        [pytest.approx(1000.0), pytest.approx(9400.0)]


@pytest.mark.parametrize("ctx", [1, 63, 64, 65, 700])
def test_paged_attention_bytes_match_the_kernel_byte_model(ctx):
    from repro.kernels.paged_attention.ops import attention_kv_bytes_per_step
    m = BT.TINY_MODEL
    kv = attention_kv_bytes_per_step(
        [ctx], page_size=64, max_len=1024, kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], dtype_bytes=2, impl="paged")
    qo = 2 * m["num_heads"] * m["head_dim"] * 2
    assert roofline.paged_attention_bytes(m, 64, ctx) == \
        (kv + qo) * m["num_layers"]


def test_flops_of_a_decoded_token():
    m = BT.TINY_MODEL
    D, H, K, hd, F, V = 128, 4, 2, 32, 256, 512
    lin = 2 * (D * H * hd * 2 + 2 * D * K * hd + 3 * D * F) * 2
    assert roofline.decode_flops(m, 10) == lin + 4 * H * hd * 10 * 2 + 2 * D * V


def test_peaks_are_keyed_by_device_kind():
    assert roofline.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")


def test_weights_are_made_again_layer_by_layer():
    m = BT.TINY_MODEL
    seed = 2 ** 31 + 11
    p = weights.make_params(m, seed)
    for name in ("blocks/0/attn/wq", "blocks/0/mlp/w_down"):
        for r in range(m["num_layers"]):
            np.testing.assert_array_equal(
                np.asarray(weights.make_leaf(m, seed, name, r), np.float32),
                np.asarray(p[name][r], np.float32))
    np.testing.assert_array_equal(
        np.asarray(weights.make_leaf(m, seed, "head/w"), np.float32),
        np.asarray(p["head/w"], np.float32))
    q = weights.make_params(m, seed + 1)
    assert not np.array_equal(np.asarray(p["head/w"], np.float32),
                              np.asarray(q["head/w"], np.float32))


def test_weight_layout_matches_the_program():
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    for m in (BT.TINY_MODEL, dict(BT.TINY_MODEL, mlp_kind="gelu",
                                  num_kv_heads=1)):
        model = build_model(ModelConfig(**m))
        weights.check_layout(weights.layout(m), {
            k: v.shape for k, v in model.init_shapes().items()})
    with pytest.raises(ValueError):
        weights.check_layout(weights.layout(BT.TINY_MODEL), {"x": (1,)})
    assert jax.numpy.dtype(BT.TINY_MODEL["dtype"]) == jax.numpy.bfloat16
