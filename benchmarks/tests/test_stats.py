"""The two token rates and the percentile arithmetic on hand-made
records, and the open-loop rate on whole cycles of the traffic files."""
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


# window [10, 20): (t_send, t_done, tokens, ok) -> tokens credited
@pytest.mark.parametrize("t_send,t_done,n,ok,credit", [
    (11.0, 15.0, 50, True, 50.0),         # wholly inside: all of it
    (1.0, 9.0, 80, True, 0.0),            # wholly before
    (21.0, 24.0, 80, True, 0.0),          # wholly after
    (8.0, 12.0, 100, True, 50.0),         # warm, straddles the first edge
    (19.0, 21.0, 70, True, 35.0),         # straddles the last edge
    (5.0, 25.0, 200, True, 100.0),        # longer than the window
    (10.0, 20.0, 30, True, 30.0),         # exactly the window
    (11.0, 15.0, 50, False, 0.0),         # failed: nothing
    (None, None, 50, False, 0.0),         # never answered: nothing
], ids=["inside", "before", "after", "first-edge", "last-edge",
        "longer-than-the-window", "the-window", "failed", "never-answered"])
def test_window_token_rate_credits_the_share_of_a_life_in_the_window(
        t_send, t_done, n, ok, credit):
    r = {"t_send": t_send, "t_done": t_done, "n_got": n if ok else 0,
         "ok": ok, "window": True}
    assert stats.window_token_rate([r], 10.0, 10.0) == \
        pytest.approx(credit / 10.0)
    # credits add up, whatever else is in the list
    other = {"t_send": 12.0, "t_done": 14.0, "n_got": 10, "ok": True,
             "window": False}
    assert stats.window_token_rate([other, r], 10.0, 10.0) == \
        pytest.approx((credit + 10.0) / 10.0)


# -- the rate on whole cycles of the benchmark's own traffic files

TICK_S, GAP_S = 0.0384, 0.01123      # ledger, PR 39: the hybrid cell


# the hybrid cell's first cut (benchmarks/traffic/mixed-length-open.json
# until PR 40 retired it): 31 requests, 8,008 tokens out, one answer of
# 1,024.  Kept here as the cycle that showed the cliff.
FIRST_CUT = {
    "rate_per_s": 0.7, "trace_seed": 20260928, "warm_seconds": 10.0,
    "prompt_tokens": {"dist": "mixture", "parts": [
        {"share": 0.75, "dist": "lognormal", "median": 256, "sigma": 1.0,
         "min": 32, "max": 2048},
        {"share": 0.25, "dist": "lognormal", "median": 8192, "sigma": 0.5,
         "min": 4096, "max": 15360}]},
    "output_tokens": {"dist": "lognormal", "median": 192, "sigma": 0.8,
                      "min": 32, "max": 1024},
    "gaps": {"dist": "exponential"}}


def replay(name, grow=0.0, seconds=45.0, stall=None):
    """The records an engine that keeps up would leave: every request
    sent when due, its life its chunks' ticks and a decode gap a token
    (no queueing); with ``grow`` every life lengthens through the run,
    as under a backlog; with ``stall`` = (begin, end) nothing is sent
    or answered between the two (a host that hangs): what was due or
    under way then is pushed to its end.  ``name`` is a traffic file's
    or a traffic dict."""
    import json
    import os
    from generators import open_trace
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    traffic = name
    if isinstance(name, str):
        with open(os.path.join(bench, "traffic", f"{name}.json")) as f:
            traffic = json.load(f)
    plan = open_trace.schedule(traffic, 7, seconds, 19200)
    t0 = 1000.0
    recs = []
    for r in plan["requests"]:
        life = (-(-len(r["prompt"]) // 256) * TICK_S
                + r["max_new"] * GAP_S)
        life *= 1.0 + grow * max(0.0, r["due"] + traffic["warm_seconds"])
        send = r["due"]
        if stall and stall[0] <= send < stall[1]:
            send = stall[1]
        if stall and send < stall[1] and send + life > stall[0]:
            life += stall[1] - max(send, stall[0])
        recs.append({"i": r["i"], "window": r["window"], "ok": True,
                     "t_due": t0 + r["due"], "t_send": t0 + send,
                     "t_done": t0 + send + life,
                     "n_got": r["max_new"], "max_new": r["max_new"]})
    offered = plan["offered"]["output_tokens"] / seconds
    return recs, t0, offered


def arrival_rate(records, t0, seconds):
    """The definition until PR 40, kept here as the witness: an answer's
    tokens credited whole at the instant of its last byte."""
    return sum(r["n_got"] for r in records
               if r["ok"] and t0 <= r["t_done"] < t0 + seconds) / seconds


@pytest.mark.parametrize("name", [
    "chat-trace-open", "moe-decode-open", "mixed-length-open-v2",
    "ssm-chat-open", "reason-long-open"])
def test_a_stationary_cyclic_trace_reads_its_offered_rate(name):
    recs, t0, offered = replay(name)
    assert stats.window_token_rate(recs, t0, 45.0) == \
        pytest.approx(offered, rel=0.02)
    # and under a backlog, lives growing by a fifth a second, it reads less
    recs, t0, offered = replay(name, grow=0.2)
    assert stats.window_token_rate(recs, t0, 45.0) < 0.9 * offered


@pytest.mark.parametrize("stall,low,high", [
    ((-3.0, 0.0), 1.03, 1.20),      # in the warm traffic: reads ABOVE
    ((10.0, 13.0), 0.99, 1.01),     # inside, drained inside: the rate
    ((41.0, 44.0), 0.85, 0.97),     # at the last edge: reads below
], ids=["before-t0", "inside", "at-the-last-edge"])
def test_a_stall_moves_the_rate_by_the_work_it_moves_across_an_edge(
        stall, low, high):
    """The life-share rate is a model, not a count of delivered tokens:
    it spreads an answer's tokens evenly over send to last byte.  A
    host that hangs for 3 s pushes lives across an edge: work that was
    due before the window is done inside it (my chip run 6, PR 40: a
    2.99 s stall in the warm traffic read 294.65 of 274.04 offered), or
    work due inside it after it.  So the guard moves with a disturbance
    in either direction; a run's generator lateness says when."""
    recs, t0, offered = replay("mixed-length-open-v2", stall=stall)
    assert low < stats.window_token_rate(recs, t0, 45.0) / offered < high


def test_no_one_arrival_moves_the_rate_as_the_old_cycle_showed():
    """The hybrid cell's first cut: 31 requests, 8,008 tokens, one answer of
    1,024 (12.8%).  An answer's last byte moving 0.2 s across either
    edge of the window moved the old rate by all its tokens; it moves
    the new one by its tokens times 0.1 s over its life, and a request
    emits no faster than a token a decode gap."""
    recs, t0, offered = replay(FIRST_CUT)
    assert offered == pytest.approx(8008 / 45.0)
    worst_new = worst_old = 0.0
    for k, r in enumerate(recs):
        life = r["t_done"] - r["t_send"]
        for edge in (t0, t0 + 45.0):
            rates = []
            for t_done in (edge - 0.1, edge + 0.1):
                moved = dict(r, t_send=edge - 0.1 - life, t_done=t_done)
                others = recs[:k] + recs[k + 1:]
                rates.append((stats.window_token_rate(
                    others + [moved], t0, 45.0),
                    arrival_rate(others + [moved], t0, 45.0)))
            worst_new = max(worst_new, abs(rates[1][0] - rates[0][0]))
            worst_old = max(worst_old, abs(rates[1][1] - rates[0][1]))
    assert worst_new < 0.005 * offered
    assert worst_old == pytest.approx(1024 / 45.0)        # 12.8%
    assert worst_old / offered == pytest.approx(0.128, abs=0.001)


def test_open_latency_runs_from_the_due_time():
    recs = [{"window": True, "ok": True, "t_due": 1.0, "t_send": 1.5,
             "t_done": 3.0},
            {"window": False, "ok": True, "t_due": 0.0, "t_send": 0.0,
             "t_done": 9.0},
            {"window": True, "ok": False, "t_due": 2.0, "t_send": 2.0,
             "t_done": 2.1}]
    assert stats.open_latencies(recs) == [2.0]


def test_costs_match_the_configuration_files():
    import importlib
    import json
    import os
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for name in os.listdir(os.path.join(bench, "configs")):
        with open(os.path.join(bench, "configs", name)) as f:
            conf = json.load(f)
        if conf["run"].get("arch"):
            # an architecture of its own counts in archs/<arch>.py, held
            # by test_serve_arch / _hybrid / _ssm / _latent
            arch = importlib.import_module(f"archs.{conf['run']['arch']}")
            assert arch.param_count(conf) == conf["memory"]["parameters"], name
            continue
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
