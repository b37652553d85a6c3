"""``trace_reduce.py`` on a small xplane recorded on the v5e (PR 23,
``.scratch/mktrace.py``: three bursts of five 2048x2048 bf16 matmuls,
each followed by a 20 ms sleep under a ``bench/nap`` span and one small
reduction, all inside ``bench/trace_window``), and the per-layer
readers on the reduced trace and hand-made counters."""
import importlib
import os

import pytest

import trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_tpu.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return trace_reduce.reduce_xplane(DATA)


def test_interval_arithmetic():
    u = trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)] and trace_reduce.total(u) == 6
    assert trace_reduce.clip(u, 2, 6) == [(2, 3), (5, 6)]
    assert trace_reduce.subtract([(0, 10)], [(2, 3), (5, 8)]) == \
        [(0, 2), (3, 5), (8, 10)]
    assert trace_reduce.subtract([(0, 4)], [(0, 4)]) == []


def test_stable_names_come_from_the_hlo_text():
    f = trace_reduce.stable_name
    assert f("%fusion.617 = f32[12]{0:T(128)} fusion(f32[12] %x), "
             "kind=kLoop") == "fusion.617_f32_12_"
    assert f("%while.3 = (s32[]{:T(128)}, f32[16384,4096]{1,0}) while(%t)") \
        == "while.3_s32_"
    assert f("%splash_mha_fwd.2 = bf16[4,32,4096,128]{3,2,1,0} custom-call()") \
        == "splash_mha_fwd.2_bf16_4_32_4096_128_"
    assert f("no hlo here") == "no_hlo_here"


def test_busy_is_the_union_of_op_intervals_inside_the_window(reduced):
    r = reduced
    assert r["devices"] == 1
    # the window is the bench/trace_window span: three naps of 20 ms and
    # three short bursts
    assert 0.060 < r["window_s"] < 0.080
    mods = r["modules"]["jit__lambda"]
    assert mods["count"] >= 10
    # ops of one stream never overlap: busy = their sum = the programs'
    assert r["busy_s"] == pytest.approx(sum(r["ops"].values()), rel=1e-6)
    assert r["busy_s"] == pytest.approx(mods["total_s"], rel=0.02)
    assert r["busy_s"] < 0.05 * r["window_s"]
    assert r["collective_s"] == 0.0 and r["collective_exposed_s"] == 0.0


def test_idle_gaps_go_to_the_host_span_that_covers_them(reduced):
    gaps = reduced["idle_gaps"]
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)
    # the sleeps (3 x 20 ms) dominate, and they are named
    assert gaps["host:bench_nap"] > 0.055
    assert gaps["host:bench_nap"] > 0.9 * sum(gaps.values())
    assert [round(d, 2) for _s, d in reduced["spans"]["bench/nap"]] == \
        [0.02, 0.02, 0.02]
    bd = trace_reduce.breakdown(reduced)
    assert bd["idle_gaps"][0][0] == "host:bench_nap"
    assert bd["device_ops"][0][0] == "convolution_reduce_fusion_bf16_"
    assert len(bd["device_ops"]) <= 10


def ctx_for(reduced, counters, conf=None):
    import costs
    return {"trace": reduced, "counters": counters, "records": [],
            "conf": conf or {}, "peak": costs.peaks("TPU v5 lite")}


def read(name, ctx):
    return importlib.import_module(f"layer_metrics.{name}").read(ctx)


def test_readers_on_the_reduced_trace_and_counters(reduced):
    ctx = ctx_for(reduced, {"window_s": 10.0, "prefill_stall_s": 0.5,
                            "kv_prefill_tokens_skipped": 300,
                            "prompt_tokens_submitted": 400,
                            "queue_depth_samples": [0, 2, 4],
                            "active_slots_samples": [12, 10],
                            "compiles_in_window": 0,
                            "gateway_overheads_s": [0.1, 0.3, 0.2]})
    idle = read("serve_device_idle_share", ctx)
    assert idle == pytest.approx(
        100 * (1 - reduced["busy_s"] / reduced["window_s"]))
    assert read("train_device_idle_share", ctx) == idle
    assert read("prefill_stall_share", ctx) == 5.0
    assert read("kv_prefix_token_hit_share", ctx) == 75.0
    assert read("engine_queue_depth_mean", ctx) == 2.0
    assert read("active_slots_mean", ctx) == 11.0
    assert read("serve_compiles_in_window", ctx) == 0
    assert read("gateway_overhead_p50_s", ctx) == 0.2
    assert read("train_collective_exposed_share", ctx) == 0.0
    # no step program in this trace: nothing to read, so no value
    assert read("train_step_mfu", ctx_for(reduced, {"seq": 1})) is None
    assert read("decode_step_roofline", ctx) is None
    assert read("train_attention_roofline",
                ctx_for(reduced, {"traced_steps": 4})) is None


def test_readers_without_a_trace_return_nothing():
    ctx = ctx_for(None, {})
    for name in ("serve_device_idle_share", "train_step_mfu",
                 "train_attention_roofline", "decode_step_roofline",
                 "train_collective_exposed_share", "prefill_stall_share",
                 "kv_prefix_token_hit_share", "train_host_phase_share",
                 "train_delta_bytes_per_step", "session_latency_p50_s"):
        assert read(name, ctx) is None, name


def test_train_readers_on_hand_made_counters():
    c = {"phase_data_wait_s": 3.0, "phase_hooks_s": 0.5, "paced_s": 2.5,
         "step_s": 50.0, "delta_bytes": 8e9, "window_steps": 100}
    ctx = ctx_for(None, c)
    assert read("train_host_phase_share", ctx) == pytest.approx(2.0)
    assert read("train_delta_bytes_per_step", ctx) == pytest.approx(80.0)
    recs = [{"ok": True, "t_send": 0.0, "t_done": float(i)}
            for i in range(1, 11)]
    ctx["records"] = recs
    assert read("session_latency_p50_s", ctx) == 5.0
    assert read("session_latency_p90_s", ctx) == 9.0
