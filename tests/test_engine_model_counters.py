"""What the model's layers sow reaches ``stats()``: golden values.

One fixed greedy script a toy configuration, through a paged engine on
the CPU: a cold group of two, a chunked admission, a prefix hit on its
chain, every answer decoded to its end (``steps_per_sync`` 4).  Every
model-counter key of ``stats()`` is then held to the value PR 43's
engine gave (recorded there, before the counters moved out of the
batcher, PR 44): counts are integers or exact float32 sums and compare
exactly, the load ratio to 1e-6.  The phases run one after another and
the cold group is enqueued from the engine thread, so which slots are
live in which program is the script's alone, never the scheduler's.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.models.transformer import TransformerConfig, TransformerLM
from edl_tpu.serving import cache_layout
from edl_tpu.serving.engine import ContinuousBatcher
from tests.test_exaone_moe import CFG as EXAONE
from tests.test_granite_moe_hybrid import CFG as GRANITE
from tests.test_kimi_linear import CFG as KIMI
from tests.test_olmoe import CFG as OLMOE
from tests.test_pangu_ultra_moe import CFG as PANGU
from tests.test_phi4flash import CFG as PHI

DENSE = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                          num_heads=4, mlp_dim=64, max_len=96, remat=False,
                          dtype=jnp.float32)


def _cut(cfg, layers):
    """Layers ``layers`` of a planned stack: every kind it has, in a
    stack that compiles in seconds."""
    pick = lambda plan: tuple(plan[i] for i in layers) if plan else ()
    return dataclasses.replace(cfg, num_layers=len(layers),
                               layer_attn=pick(cfg.layer_attn),
                               layer_mlp=pick(cfg.layer_mlp))


CONFIGS = {
    "dense": DENSE,
    "olmoe_dropless": OLMOE,
    "moe_capacity": dataclasses.replace(DENSE, moe_experts=4, moe_top_k=2,
                                        moe_capacity=0.05),
    # window x 3 then global, a dense layer then held experts
    "exaone_window_held": _cut(EXAONE, range(4)),
    # ssm, ssm, global, ssm
    "granite_ssm": _cut(GRANITE, range(3, 7)),
    # kda x 3 then latent
    "kimi_kda_latent": _cut(KIMI, range(4)),
    # latent x 3 with a low-rank query
    "pangu_latent": PANGU,
    # mamba1, window, mamba1, window, mamba1 (emits), global (lends),
    # gmu (owns nothing), cross (borrows): the last-position cut
    "phi_borrowed": PHI,
}

MODEL_KEYS = ("moe_", "latent_", "ssm_", "decode_kv_tokens_", "borrowed_",
              "prefill_layer_",
              "kv_slot_bytes_", "kv_state_", "kv_window_", "kv_prefix_",
              "kv_prefill_tokens")

# every stats() key of a paged engine without speculative decoding at PR
# 45 (PR 43's and the borrowing layers' and the last-position cut's): the same set for all seven configurations (the benchmark's readers
# are the contract); since PR 50 the two keys of the program-build
# ledger (what the engine thread built) beside them
ALL_KEYS = frozenset("""
program_builds program_build_s
active_slots admitted borrowed_kv_tokens_live borrowed_kv_tokens_prefill
borrowed_kv_tokens_read chunk_lane_busy_s chunk_pair_dispatches
chunked_admissions
prefill_layer_visits prefill_layer_visits_cut
decode_kv_tokens_live decode_kv_tokens_slab decode_kv_tokens_window_need
decode_kv_tokens_window_read decode_s_sum decode_tokens device_enqueues
device_queue_programs_sum draining first_tokens idle_wait_s kv_block
kv_blocks_free kv_blocks_used kv_commit_skips kv_evictions kv_prefill_pairs
kv_prefill_rows_live kv_prefill_rows_read kv_prefill_tokens
kv_prefill_tokens_skipped kv_prefix_hits kv_prefix_misses kv_sessions
kv_slot_bytes_global kv_slot_bytes_latent kv_slot_bytes_state
kv_slot_bytes_window kv_state_reprefill_tokens kv_state_snapshot_skips
kv_state_snapshots kv_window_snapshot_skips kv_window_snapshots
latent_decode_calls latent_prefill_calls latent_prefill_kernel_calls
latent_prefill_pairs latent_prefill_rows_live latent_prefill_rows_read
latent_prefill_tokens latent_tokens_live latent_tokens_read
lookahead_discarded_token_steps lookahead_ticks max_prompt_len
moe_assignments moe_assignments_routed moe_decode_experts_fetched
moe_decode_experts_touched moe_decode_layer_steps moe_prefill_drops
moe_prefill_experts_touched moe_prefill_groups moe_prefill_max_load_sum
moe_prefix_kernel_calls moe_tokens prefill_chunk prefill_chunks
prefill_stall_s queue_depth queue_wait_cause_group_s queue_wait_cause_lane_s
queue_wait_cause_slots_s queue_wait_cause_tick_s queue_wait_s_sum
requests_done slot_utilization slots ssm_prefill_positions
ssm_prefill_positions_pad ssm_state_steps ssm_state_steps_run stage_decode_n
stage_decode_sum_s stage_deliver_n stage_deliver_sum_s stage_prefill_chunk_n
stage_prefill_chunk_sum_s stage_prefill_cold_n stage_prefill_cold_sum_s
stage_prefill_n stage_prefill_reuse_n stage_prefill_reuse_sum_s
stage_prefill_sum_s stage_queue_wait_chunk_n stage_queue_wait_chunk_sum_s
stage_queue_wait_cold_n stage_queue_wait_cold_sum_s
stage_queue_wait_le_0.002 stage_queue_wait_le_0.003
stage_queue_wait_le_0.0045 stage_queue_wait_le_0.00675
stage_queue_wait_le_0.0101 stage_queue_wait_le_0.0152
stage_queue_wait_le_0.0228 stage_queue_wait_le_0.0342
stage_queue_wait_le_0.0513 stage_queue_wait_le_0.0769
stage_queue_wait_le_0.115 stage_queue_wait_le_0.173
stage_queue_wait_le_0.259 stage_queue_wait_le_0.389
stage_queue_wait_le_0.584 stage_queue_wait_le_0.876 stage_queue_wait_le_1.31
stage_queue_wait_le_1.97 stage_queue_wait_le_15 stage_queue_wait_le_2.96
stage_queue_wait_le_22.4 stage_queue_wait_le_33.7 stage_queue_wait_le_4.43
stage_queue_wait_le_50.5 stage_queue_wait_le_6.65 stage_queue_wait_le_9.98
stage_queue_wait_le_inf stage_queue_wait_n stage_queue_wait_reuse_n
stage_queue_wait_reuse_sum_s stage_queue_wait_sum_s stage_ttft_le_0.002
stage_ttft_le_0.003 stage_ttft_le_0.0045 stage_ttft_le_0.00675
stage_ttft_le_0.0101 stage_ttft_le_0.0152 stage_ttft_le_0.0228
stage_ttft_le_0.0342 stage_ttft_le_0.0513 stage_ttft_le_0.0769
stage_ttft_le_0.115 stage_ttft_le_0.173 stage_ttft_le_0.259
stage_ttft_le_0.389 stage_ttft_le_0.584 stage_ttft_le_0.876
stage_ttft_le_1.31 stage_ttft_le_1.97 stage_ttft_le_15 stage_ttft_le_2.96
stage_ttft_le_22.4 stage_ttft_le_33.7 stage_ttft_le_4.43 stage_ttft_le_50.5
stage_ttft_le_6.65 stage_ttft_le_9.98 stage_ttft_le_inf stage_ttft_n
stage_ttft_sum_s tick_admit_s tick_coverage tick_dispatch_s tick_finish_s
tick_kv_commit_s tick_s tick_sync_s tick_tasks_s ticks tokens_emitted
tokens_per_s ttft_s_sum uptime_s
""".split())

# every model-counter key; a configuration's entry gives what is not 0
COUNTER_KEYS = (
    'decode_kv_tokens_live', 'decode_kv_tokens_slab',
    'decode_kv_tokens_window_read', 'decode_kv_tokens_window_need',
    'kv_slot_bytes_window', 'kv_slot_bytes_global', 'kv_slot_bytes_state',
    'kv_slot_bytes_latent', 'latent_tokens_live', 'latent_tokens_read',
    'latent_prefill_rows_live', 'latent_prefill_rows_read',
    'latent_prefill_calls', 'latent_prefill_kernel_calls',
    'latent_decode_calls', 'latent_prefill_pairs', 'latent_prefill_tokens',
    'ssm_state_steps', 'ssm_state_steps_run', 'ssm_prefill_positions',
    'ssm_prefill_positions_pad', 'moe_prefill_drops', 'moe_tokens',
    'moe_assignments', 'moe_assignments_routed', 'moe_decode_layer_steps',
    'moe_decode_experts_touched', 'moe_decode_experts_fetched',
    'moe_prefill_groups', 'moe_prefix_kernel_calls',
    'moe_prefill_experts_touched', 'moe_prefill_max_load_sum',
    'kv_prefix_hits', 'kv_prefix_misses', 'kv_prefill_tokens',
    'kv_prefill_tokens_skipped', 'kv_window_snapshots',
    'kv_window_snapshot_skips', 'kv_state_snapshots',
    'kv_state_snapshot_skips', 'kv_state_reprefill_tokens',
    'borrowed_kv_tokens_live', 'borrowed_kv_tokens_read',
    'borrowed_kv_tokens_prefill', 'prefill_layer_visits',
    'prefill_layer_visits_cut')

GOLDEN = {
    # recorded by PR 45, which added the configuration and its keys
    'phi_borrowed': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'decode_kv_tokens_window_read': 532, 'decode_kv_tokens_window_need':
        224, 'kv_slot_bytes_window': 4864, 'kv_slot_bytes_global': 12288,
        'kv_slot_bytes_state': 5376, 'ssm_state_steps': 84,
        'ssm_state_steps_run': 180.0, 'ssm_prefill_positions': 88,
        'ssm_prefill_positions_pad': 16, 'borrowed_kv_tokens_live': 850,
        'borrowed_kv_tokens_read': 5760.0, 'borrowed_kv_tokens_prefill': 112,
        # 72 tokens below the tail (6 layers), the tail (2) at the four
        # sampling lanes' rows
        'prefill_layer_visits': 440, 'prefill_layer_visits_cut': 136,
        'kv_prefix_hits': 1, 'kv_prefix_misses': 3, 'kv_prefill_tokens': 112,
        'kv_prefill_tokens_skipped': 40, 'kv_window_snapshots': 3,
        'kv_state_snapshots': 3
    },
    'dense': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'kv_slot_bytes_global': 49152, 'kv_prefix_hits': 1,
        'kv_prefix_misses': 3, 'kv_prefill_tokens': 112,
        'kv_prefill_tokens_skipped': 40
    },
    'exaone_window_held': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'decode_kv_tokens_window_read': 532, 'decode_kv_tokens_window_need':
        224, 'kv_slot_bytes_window': 14592, 'kv_slot_bytes_global': 24576,
        'moe_tokens': 100, 'moe_assignments': 315, 'moe_assignments_routed':
        1200, 'moe_decode_layer_steps': 60, 'moe_decode_experts_touched':
        82, 'moe_prefill_groups': 15, 'moe_prefill_experts_touched': 53,
        'moe_prefill_max_load_sum': 27.789, 'kv_prefix_hits': 1,
        'kv_prefix_misses': 3, 'kv_prefill_tokens': 112,
        'kv_prefill_tokens_skipped': 40, 'kv_window_snapshots': 6
    },
    'granite_ssm': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'kv_slot_bytes_global': 12288, 'kv_slot_bytes_state': 9024,
        'ssm_state_steps': 84, 'ssm_state_steps_run': 180.0,
        'ssm_prefill_positions': 88, 'ssm_prefill_positions_pad': 16,
        'moe_tokens': 100, 'moe_assignments': 621, 'moe_assignments_routed':
        1200, 'moe_decode_layer_steps': 80, 'moe_decode_experts_touched':
        170, 'moe_prefill_groups': 20, 'moe_prefill_experts_touched': 79,
        'moe_prefill_max_load_sum': 27.82, 'kv_prefix_hits': 1,
        'kv_prefix_misses': 3, 'kv_prefill_tokens': 112,
        'kv_prefill_tokens_skipped': 40, 'kv_window_snapshots': 3,
        'kv_state_snapshots': 3
    },
    'kimi_kda_latent': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'kv_slot_bytes_state': 9600, 'kv_slot_bytes_latent': 49152,
        'latent_tokens_live': 850, 'latent_tokens_read': 5760.0,
        'latent_prefill_rows_live': 176, 'latent_prefill_rows_read': 576,
        'latent_prefill_calls': 6, 'latent_decode_calls': 28,
        'latent_prefill_pairs': 1326, 'latent_prefill_tokens': 72,
        'ssm_state_steps': 84, 'ssm_state_steps_run': 180.0,
        'ssm_prefill_positions': 88, 'ssm_prefill_positions_pad': 16,
        'moe_tokens': 100, 'moe_assignments': 260, 'moe_assignments_routed':
        900, 'moe_decode_layer_steps': 60, 'moe_decode_experts_touched': 59,
        'moe_prefill_groups': 15, 'moe_prefill_experts_touched': 30,
        'moe_prefill_max_load_sum': 19.81, 'kv_prefix_hits': 1,
        'kv_prefix_misses': 3, 'kv_prefill_tokens': 112,
        'kv_prefill_tokens_skipped': 40, 'kv_window_snapshots': 3,
        'kv_state_snapshots': 3
    },
    'moe_capacity': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'kv_slot_bytes_global': 49152, 'moe_prefill_drops': 243,
        'moe_tokens': 72, 'kv_prefix_hits': 1, 'kv_prefix_misses': 3,
        'kv_prefill_tokens': 112, 'kv_prefill_tokens_skipped': 40
    },
    'olmoe_dropless': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'kv_slot_bytes_global': 98304, 'moe_tokens': 100, 'moe_assignments':
        400, 'moe_assignments_routed': 400, 'moe_decode_layer_steps': 40,
        'moe_decode_experts_touched': 104, 'moe_prefill_groups': 10,
        'moe_prefill_experts_touched': 56, 'moe_prefill_max_load_sum':
        26.548, 'kv_prefix_hits': 1, 'kv_prefix_misses': 3,
        'kv_prefill_tokens': 112, 'kv_prefill_tokens_skipped': 40
    },
    'pangu_latent': {
        'decode_kv_tokens_live': 850, 'decode_kv_tokens_slab': 5760,
        'kv_slot_bytes_latent': 147456, 'latent_tokens_live': 2550,
        'latent_tokens_read': 17280.0, 'latent_prefill_rows_live': 528,
        'latent_prefill_rows_read': 1728, 'latent_prefill_calls': 18,
        'latent_decode_calls': 84, 'latent_prefill_pairs': 3978,
        'latent_prefill_tokens': 72, 'moe_tokens': 100, 'moe_assignments':
        73, 'moe_assignments_routed': 600, 'moe_decode_layer_steps': 40,
        'moe_decode_experts_touched': 21, 'moe_prefill_groups': 10,
        'moe_prefill_experts_touched': 10, 'moe_prefill_max_load_sum':
        12.015, 'kv_prefix_hits': 1, 'kv_prefix_misses': 3,
        'kv_prefill_tokens': 112, 'kv_prefill_tokens_skipped': 40
    },
}


def _stats(name):
    cfg = CONFIGS[name]
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(11)
    tok = lambda n: rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
    a, b, doc, more = tok(11), tok(13), tok(41), tok(6)
    eng = ContinuousBatcher(
        cfg, params, slots=3, max_len=96, temperature=0.0, steps_per_sync=4,
        kv_block=8, kv_pool_blocks=48, prefill_chunk=16,
        prefill_buckets=(8, 16, 32))
    try:
        # both in the queue before the engine thread looks: one group
        for fut in eng.run_on_engine(
                lambda: [eng.submit(a, 6), eng.submit(b, 6)]):
            assert len(fut.result(300)) == 6
        assert len(eng.submit(doc, 5).result(300)) == 5
        assert len(eng.submit(np.concatenate([doc, more]), 7).result(300)) == 7
        # a future resolves before its tick's counters are booked: a
        # task runs between ticks, behind them
        eng.run_on_engine(lambda: None)
        step = jax.eval_shape(
            eng._step_impl, eng._cache, eng._toks, jax.random.key(0),
            eng._params, jnp.ones((3,), bool))
        return eng.stats(), step, eng._counters.layout
    finally:
        eng.stop()


@pytest.fixture(scope="module", params=sorted(CONFIGS))
def ran(request):
    return request.param, *_stats(request.param)


@pytest.fixture
def run(ran):
    return ran[:2]


def test_model_counters_equal_the_recorded_values(run):
    name, stats = run
    assert stats["kv_prefix_hits"] == 1 and stats["chunked_admissions"] == 1
    got = {k: v for k, v in stats.items() if k.startswith(MODEL_KEYS)}
    assert sorted(got) == sorted(COUNTER_KEYS)
    for key in COUNTER_KEYS:
        want = GOLDEN[name].get(key, 0)
        if key == "moe_prefill_max_load_sum":       # a sum of ratios
            assert got[key] == pytest.approx(want, rel=1e-6), key
        else:
            assert got[key] == want, key


def test_stats_has_the_keys_it_had(run):
    name, stats = run
    assert set(stats) == ALL_KEYS, (
        sorted(set(stats) - ALL_KEYS), sorted(ALL_KEYS - set(stats)))


# what each configuration's layers sow, as the engine found it: (name,
# width) pairs, discovered from the model (CPU: no kernel counts itself)
LAYOUTS = {
    "dense": (),
    "moe_capacity": (("moe_drops", 1),),
    "olmoe_dropless": (("moe_stats", 3),),
    "exaone_window_held": (("moe_stats", 4),),
    "granite_ssm": (("moe_stats", 4), ("ssm_slots_run", 1)),
    "kimi_kda_latent": (("latent_tokens_read", 1), ("moe_stats", 4),
                        ("ssm_slots_run", 1)),
    "pangu_latent": (("latent_tokens_read", 1), ("moe_stats", 4)),
    "phi_borrowed": (("borrowed_rows_read", 1), ("ssm_slots_run", 1)),
}


def test_a_step_program_returns_one_counters_leaf(ran):
    """``(cache, last, tokens, counters)`` whatever the layers sow: one
    float32 vector, a sum and a count of calls for every sown name."""
    name, _, step, layout = ran
    assert layout == LAYOUTS[name]
    cache, last, tokens, counters = step
    assert tokens.shape == (3, 4) and last.shape == (3,)
    (leaf,) = jax.tree.leaves(counters)
    assert leaf.dtype == jnp.float32
    assert leaf.shape == (sum(w + 1 for _, w in layout),)


KINDS = {"global", "window", "ssm", "kda", "latent", "mamba1", "gmu", "cross"}


def test_the_engine_names_no_mixer_kind():
    """``serving/engine.py`` schedules; what a layer's kind means is
    ``cache_layout``'s and ``model_counters``' to say.  No expression of
    it holds a kind's name (a message may), reads a per-kind field of
    the configuration or imports from ``edl_tpu.ops``."""
    import ast
    import pathlib

    import edl_tpu.serving.engine as engine
    tree = ast.parse(pathlib.Path(engine.__file__).read_text())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and node.value in KINDS:
            found.append((node.lineno, node.value))
        elif isinstance(node, ast.Attribute) and (
                node.attr.startswith(("mla_", "ssm_", "kda_"))
                or node.attr in ("moe_held", "attn_kind", "layer_attn")):
            found.append((node.lineno, node.attr))
        elif isinstance(node, ast.ImportFrom) and (
                node.module or "").startswith("edl_tpu.ops"):
            found.append((node.lineno, node.module))
        elif isinstance(node, ast.Import):
            found += [(node.lineno, a.name) for a in node.names
                      if a.name.startswith("edl_tpu.ops")]
    assert not found, found
    assert KINDS == set(cache_layout._KINDS)    # the names looked for


if __name__ == "__main__":       # record: python -m tests.test_engine_model_counters
    import json
    out, keys = {}, {}
    for name in sorted(CONFIGS):
        stats, *_ = _stats(name)
        out[name] = {k: v for k, v in stats.items()
                     if k.startswith(MODEL_KEYS)}
        keys[name] = sorted(stats)
    print(json.dumps({"golden": out, "keys": keys}))
