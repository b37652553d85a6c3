"""Every seed offers the same work: the same multiset of lengths and
gaps, another start point and other token ids."""
import json
import os

import numpy as np
import pytest

from generators import closed_sessions, lm_packed, open_trace

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 7, 2_147_483_659)       # the last is over 2**31


def traffic(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def window(plan):
    return [r for r in plan["requests"] if r["window"]]


def test_open_trace_same_multiset_under_any_seed():
    t = traffic("chat-trace-open")
    plans = [open_trace.schedule(t, s, 45.0, 32768) for s in SEEDS]
    pairs = [sorted((len(r["prompt"]), r["max_new"]) for r in window(p))
             for p in plans]
    assert pairs[0] == pairs[1] == pairs[2]
    gaps = []
    for p in plans:
        due = [r["due"] for r in window(p)] + [45.0]
        gaps.append(sorted(np.round(np.diff(due), 9)))
    assert gaps[0] == gaps[1] == gaps[2]
    assert len(window(plans[0])) == round(t["rate_per_s"] * 45.0)
    # every seed replays the cycle from its first request, at the same
    # instants; only the token ids differ
    for p in plans[1:]:
        assert [(r["i"], r["due"], len(r["prompt"]), r["max_new"])
                for r in p["requests"]] == \
            [(r["i"], r["due"], len(r["prompt"]), r["max_new"])
             for r in plans[0]["requests"]]
    assert window(plans[0])[0]["prompt"] != window(plans[1])[0]["prompt"]
    # the same seed gives the same inputs
    again = open_trace.schedule(t, SEEDS[1], 45.0, 32768)
    assert again == plans[1]


def test_open_trace_warm_traffic_is_the_cycle_walked_backwards():
    t = traffic("chat-trace-open")
    plan = open_trace.schedule(t, 3, 45.0, 32768)
    warm = [r for r in plan["requests"] if not r["window"]]
    assert warm and all(r["due"] < 0 for r in warm)
    assert -warm[0]["due"] >= t["warm_seconds"]
    due = [r["due"] for r in plan["requests"]]
    assert due == sorted(due) and 0 <= window(plan)[-1]["due"] < 45.0
    lens = {len(r["prompt"]) for r in plan["requests"]}
    assert min(lens) >= 32 and max(lens) <= 1024


def test_open_trace_shapes_cover_every_request():
    t = traffic("chat-trace-open")
    sh = open_trace.shapes(t, 45.0, 16)
    plan = open_trace.schedule(t, 5, 45.0, 32768)
    assert {len(r["prompt"]) for r in window(plan)} <= set(sh["prompt_lens"])
    need = {(len(r["prompt"]) + r["max_new"] - 1) // 16 for r in window(plan)}
    assert need == set(sh["commit_block_counts"])


def test_closed_sessions_same_lengths_under_any_seed():
    t = traffic("doc-qa-closed")
    plans = [closed_sessions.schedule(t, s, 45.0, 32768) for s in SEEDS]
    assert plans[0]["doc_lens"] == plans[1]["doc_lens"] == plans[2]["doc_lens"]
    assert plans[0]["question_lens"] == plans[2]["question_lens"]
    assert min(plans[0]["doc_lens"]) >= 3072
    assert max(plans[0]["doc_lens"]) <= 6144
    starts = [[c["first_session"] for c in p["clients"]] for p in plans]
    assert len({tuple(s) for s in starts}) > 1
    assert sorted(c["first_turn"] for c in plans[0]["clients"]) == \
        [0, 0, 1, 1, 2, 2, 3, 3]
    a = closed_sessions.session_ids(plans[0], 4, 0, 0)
    b = closed_sessions.session_ids(plans[1], 4, 0, 0)
    assert len(a[0]) == len(b[0]) and a[0] != b[0]
    assert closed_sessions.session_ids(plans[0], 4, 0, 0) == a
    assert closed_sessions.session_ids(plans[0], 4, 1, 0)[0] != a[0]
    assert closed_sessions.session_ids(plans[0], 4, 0, 1)[0] != a[0]
    sh = closed_sessions.shapes(t, 45.0, 16)
    assert sh["max_total"] <= 8192 - 1


def test_lm_packed_full_batches_and_seeded():
    t = traffic("lm-packed-4k")
    a = next(lm_packed.batches(t, 1, 2, 64, 512))["ids"]
    b = next(lm_packed.batches(t, 1, 2, 64, 512))["ids"]
    c = next(lm_packed.batches(t, 2, 2, 64, 512))["ids"]
    assert a.shape == (2, 65) and a.dtype == np.int32
    assert (a == b).all() and (a != c).any()
    assert a.min() >= 0 and a.max() < 512
    # the chain is peaked: most transitions land on one of 4 successors
    nxt = np.random.default_rng(t["trace_seed"]).integers(0, 512, (512, 4))
    hits = sum(a[r, i + 1] in nxt[a[r, i]] for r in range(2)
               for i in range(64))
    assert hits > 0.8 * 128


@pytest.mark.parametrize("seed", [-1, 1 << 70])
def test_seed_out_of_range_is_refused(seed):
    with pytest.raises(ValueError):
        open_trace.schedule(traffic("chat-trace-open"), seed, 45.0, 32768)


def test_a_listed_distribution_is_a_data_file():
    t = traffic("chat-trace-open")
    n = round(t["rate_per_s"] * 45.0)
    t["gaps"] = {"dist": "listed", "values": [1.0 + (i % 7) for i in range(n)]}
    plan = open_trace.schedule(t, 1, 45.0, 32768)
    due = [r["due"] for r in window(plan)] + [45.0]
    gaps = np.diff(due)
    assert sorted(np.round(gaps * sum(t["gaps"]["values"]) / 45.0, 6)) == \
        sorted(float(v) for v in t["gaps"]["values"])
    t["gaps"]["values"] = [1.0]
    with pytest.raises(ValueError):
        open_trace.schedule(t, 1, 45.0, 32768)


# -- every open-loop cell's cycle can carry its metrics (PR 40)

def _open_cells():
    root = os.path.dirname(BENCH)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    bound = next(m["bound"] for m in spec["end_to_end"]
                 if m["name"] == "serve_tokens_per_s")
    return [(w["name"], w["traffic"], spec["run_seconds"], bound)
            for w in spec["workloads"]
            if traffic(w["traffic"]).get("loop") == "open"]


OPEN_CELLS = _open_cells()


@pytest.mark.parametrize("cell,name,seconds,bound", OPEN_CELLS,
                         ids=[c[0] for c in OPEN_CELLS])
def test_an_open_loop_cycle_can_carry_its_metrics(cell, name, seconds, bound):
    """A median wants some tens of requests, a p90 at least 5 beyond
    it, and no one answer may be worth more of the cycle's tokens than
    the bound on the token rate (the hybrid cell's first cut, gone with
    PR 40, had 31 requests, 3 beyond p90 and one answer of 12.8%)."""
    prompts, outs, gaps = open_trace.cycle(traffic(name), float(seconds))
    n = len(prompts)
    beyond_p90 = n - int(np.ceil(0.9 * n))
    largest = outs.max() / outs.sum()
    assert n >= 50 and beyond_p90 >= 5 and largest < bound, (
        cell, n, beyond_p90, largest)
