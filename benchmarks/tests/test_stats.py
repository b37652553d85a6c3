"""The whole-request rate and the percentile arithmetic on hand-made
records."""
import pytest

import costs
import stats


def test_percentile_is_nearest_rank():
    xs = [5.0, 1.0, 3.0, 2.0, 4.0]
    assert stats.percentile(xs, 50) == 3.0
    assert stats.percentile(xs, 90) == 5.0
    assert stats.percentile(xs, 20) == 1.0
    assert stats.percentile(list(range(1, 101)), 90) == 90
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def rec(client, t_send, t_done, n=48, ok=True):
    return {"client": client, "t_send": t_send, "t_done": t_done,
            "n_got": n if ok else 0, "ok": ok}


def test_whole_request_rate_counts_only_whole_turns_per_client():
    t0, secs = 100.0, 10.0
    records = [
        rec(0, 99.0, 101.0),      # started before the window: not counted
        rec(0, 101.0, 103.0), rec(0, 103.0, 107.0),
        rec(0, 107.0, 111.0),     # finished after it: not counted
        rec(1, 100.5, 104.5), rec(1, 104.5, 108.5),
        rec(2, 102.0, 103.0, ok=False),     # failed: no tokens, no span
    ]
    # client 0: 96 tokens over 101..107; client 1: 96 over 100.5..108.5
    assert stats.whole_request_rate(records, t0, secs) == \
        pytest.approx(96 / 6.0 + 96 / 8.0)
    assert stats.whole_request_rate([], t0, secs) == 0.0


def test_window_token_rate_credits_answers_at_their_arrival():
    recs = [dict(rec(0, 1.0, 9.0), window=False),       # before
            dict(rec(0, 8.0, 12.0, n=100), window=False),   # warm, lands in
            dict(rec(0, 11.0, 15.0, n=50), window=True),
            dict(rec(0, 19.0, 21.0, n=70), window=True)]    # lands after
    assert stats.window_token_rate(recs, 10.0, 10.0) == 15.0


def test_open_latency_runs_from_the_due_time():
    recs = [{"window": True, "ok": True, "t_due": 1.0, "t_send": 1.5,
             "t_done": 3.0},
            {"window": False, "ok": True, "t_due": 0.0, "t_send": 0.0,
             "t_done": 9.0},
            {"window": True, "ok": False, "t_due": 2.0, "t_send": 2.0,
             "t_done": 2.1}]
    assert stats.open_latencies(recs) == [2.0]


def test_costs_match_the_configuration_files():
    import json
    import os
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in os.listdir(os.path.join(bench, "configs")):
        with open(os.path.join(bench, "configs", name)) as f:
            conf = json.load(f)
        assert costs.param_count(conf) == conf["memory"]["parameters"], name
    with open(os.path.join(bench, "configs",
                           "mistral-7b-v0.3-serve-d6.json")) as f:
        conf = json.load(f)
    assert costs.kv_bytes_per_token(conf) == 24 * 1024
    # 6N + attention, by hand for one layer of the d6 file
    n = costs.matmul_params(conf)
    assert costs.train_flops_per_token(conf, 4096) == \
        6 * n + 6 * 6 * 4096 * 4096
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")
    t, bound = costs.roofline_seconds(197e12, 819e9 * 2, costs.peaks("TPU v5e"))
    assert (t, bound) == (2.0, "memory")
