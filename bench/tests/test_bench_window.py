"""Window arithmetic of the end-to-end and per-layer metrics, on made-up
timestamps, and the copied percentile and arrival helpers."""

import statistics
import types

import numpy as np
import pytest

from bench import flops, stats
from bench.drivers.serve_queue import lane_spans, window_rate
from bench.run import metric_reader


def _resp(arrival, admit, first, complete):
    return types.SimpleNamespace(
        request=types.SimpleNamespace(arrival_t=arrival, admit_t=admit),
        first_result_t=first, complete_t=complete)


# Three requests queued at t=10 on one lane: each takes 4 s, the first
# token 1 s after its start.
QUEUE = [_resp(10.0, 10.1, 11.1, 14.1), _resp(10.0, 10.1, 15.1, 18.1),
         _resp(10.0, 10.1, 19.1, 22.1)]


def test_serve_rate_counts_whole_requests_inside_the_window():
    t0, rate, inside = window_rate([r.request.arrival_t for r in QUEUE],
                                   QUEUE, 10.0, 100)
    assert t0 == 10.0
    assert inside == QUEUE[:2]                 # the third ends at 12.1 s
    assert rate == pytest.approx(200 / 8.1)    # to the last completion


def test_serve_rate_refuses_an_empty_window():
    with pytest.raises(RuntimeError, match="too short"):
        window_rate([10.0], QUEUE, 2.0, 100)


def test_lane_spans_start_after_the_request_before():
    spans = lane_spans(QUEUE, QUEUE[:2])
    assert [s["start"] for s in spans] == [10.1, 14.1]
    facts = {"requests": spans, "prompt": 4, "gen": 11}
    prefill = metric_reader("serve.prefill_ms_per_token")(facts, None)
    decode = metric_reader("serve.decode_step_ms")(facts, None)
    assert prefill == pytest.approx(1000 / 4)
    assert decode == pytest.approx(3000 / 10)


@pytest.mark.parametrize("name,facts,trace,want", [
    ("device_idle.serve", {}, {"idle_share": 0.25}, 25.0),
    ("device_idle.train", {}, None, None),
    ("hosttask.device_ms_per_graph", {"graphs_traced": 4},
     {"busy_s": 0.002}, 0.5),
    ("hosttask.device_ms_per_graph", {"graphs_traced": 0},
     {"busy_s": 0.002}, None),
    ("step_mfu.train", {"traced_flops": 197e12 * 0.5,
                        "device_kind": "TPU v5 lite"}, {"busy_s": 1.0}, 50.0),
    ("step_mfu.serve", {"traced_flops": 1e12, "device_kind": "TPU v5 lite"},
     {"busy_s": 0.0}, None),
])
def test_layer_readers(name, facts, trace, want):
    got = metric_reader(name)(facts, trace)
    assert got == (pytest.approx(want) if want is not None else None)


def test_mfu_refuses_an_unknown_device():
    with pytest.raises(KeyError, match="peaks.json"):
        metric_reader("step_mfu.serve")(
            {"traced_flops": 1.0, "device_kind": "TPU v9"}, {"busy_s": 1.0})


@pytest.mark.parametrize("values", [[3.0], [1.0, 2.0], [5, 1, 4, 2, 3],
                                    [2.0] * 7, list(range(100))])
@pytest.mark.parametrize("q", [0, 50, 95, 100])
def test_nearest_rank_is_numpy_inverted_cdf(values, q):
    want = np.percentile(values, q, method="inverted_cdf") if q else min(values)
    assert stats.nearest_rank(values, q) == want


def test_nearest_rank_refuses_empty_and_out_of_range():
    with pytest.raises(ValueError):
        stats.nearest_rank([], 50)
    with pytest.raises(ValueError):
        stats.nearest_rank([1.0], 101)


def test_poisson_arrivals_repeat_per_seed():
    a = stats.poisson_arrivals(100.0, 50, seed=2**33 + 1)
    b = stats.poisson_arrivals(100.0, 50, seed=2**33 + 1)
    assert np.array_equal(a, b) and np.all(np.diff(a) > 0)
    with pytest.raises(ValueError):
        stats.poisson_arrivals(0.0, 5, seed=1)


def test_spread_is_iqr_over_median():
    values = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 12.5)


def test_flops_from_shapes():
    c = dict(hidden_size=4, intermediate_size=8, vocab_size=10,
             num_attention_heads=2, num_key_value_heads=1, head_dim=2,
             num_hidden_layers=3, sliding_window=2)
    # per layer: q 4*2*2 + k,v 2*4*1*2 + o 2*2*4 + mlp 3*4*8 = 144
    assert flops.matmul_params(c) == 3 * 144 + 40
    assert [flops.visible_keys(c, p) for p in range(4)] == [1, 2, 2, 2]
    one = flops.forward_flops(c, [0])
    assert one == 2 * 472 + 4 * 3 * 4 * 1
    assert flops.train_step_flops(c, 2, 1) == 3 * 2 * one
    assert flops.serve_request_flops(c, 1, 2, 2) == flops.forward_flops(
        c, [0, 1, 2])
