"""End-to-end arithmetic: whole window, all requests, per request."""
from types import SimpleNamespace

import pytest

from bench.harness import stats


def _res(reqs, steps=10):
    return SimpleNamespace(per_request=[
        {"new_tokens": n, "ttft_s": t, "latency_s": lat}
        for n, t, lat in reqs], steps=steps)


def test_output_tok_s_is_over_the_whole_window():
    calls = [_res([(10, 0.1, 1.0), (20, 0.2, 2.0)]), _res([(30, 0.1, 3.0)])]
    # 60 tokens over a 12 s window, not over the 5 s the calls decode
    assert stats.output_tok_s(calls, 12.0) == pytest.approx(5.0)


def test_tails_are_over_all_requests_of_all_calls():
    calls = [_res([(5, float(i), 100.0) for i in range(10)]),
             _res([(5, float(i), 100.0) for i in range(10, 20)])]
    # 20 requests, ttft 0..19: linear 90th percentile is 17.1
    assert stats.ttft_p90_s(calls) == pytest.approx(17.1)


def test_tpot_is_per_request():
    # per request (9 - 1) / (5 - 1) = 2000 ms and (2 - 1) / (3 - 1) = 500
    # ms; a 1-token request has no decode gap and is left out
    calls = [_res([(5, 1.0, 9.0), (3, 1.0, 2.0), (1, 0.5, 0.5)])]
    assert stats.tpot_p90_ms(calls) == pytest.approx(500 + 0.9 * 1500)


def test_decode_batch_mean_counts_decode_tokens_only():
    calls = [_res([(5, 0, 1), (3, 0, 1)], steps=3),
             _res([(2, 0, 1)], steps=1)]
    # (10 tokens - 3 first tokens from prefill) / 4 steps
    assert stats.decode_batch_mean(calls) == pytest.approx(7 / 4)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        stats.percentile([], 90)
