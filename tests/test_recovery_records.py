"""Recovery-time record merging (cluster/recovery.py): launcher and
trainer halves join per stage, phases compute correctly, ordering is
chronological."""

import json

from edl_tpu.cluster import paths
from edl_tpu.cluster.recovery import load_recovery_records, summarize_recovery
from edl_tpu.utils import constants


def put(kv, job, stage, role, pod, times):
    kv.put(paths.key(job, constants.ETCD_RECOVERY, f"{stage}/{role}/{pod}"),
           json.dumps(times).encode())


def test_merge_and_breakdown(memkv):
    t0 = 1000.0
    put(memkv, "j", "s1", "launcher", "podA",
        {"detect": t0, "killed": t0 + 2, "barrier": t0 + 2.5,
         "spawn": t0 + 3})
    put(memkv, "j", "s1", "trainer", "podA",
        {"restored": t0 + 8, "first_step": t0 + 9.5})
    # a second, later resize with no trainer half yet
    put(memkv, "j", "s2", "launcher", "podA",
        {"detect": t0 + 100, "killed": t0 + 101, "barrier": t0 + 101.2,
         "spawn": t0 + 101.5})

    recs = load_recovery_records(memkv, "j")
    assert set(recs) == {"s1", "s2"}

    stages = summarize_recovery(memkv, "j", kill_time=t0 - 1.5)
    assert [s["stage"] for s in stages] == ["s1", "s2"]  # chronological
    s1 = stages[0]
    assert s1["detect_to_kill"] == 2.0
    assert s1["kill_to_barrier"] == 0.5
    assert s1["barrier_to_spawn"] == 0.5
    assert s1["spawn_to_restored"] == 5.0
    assert s1["restored_to_first_step"] == 1.5
    assert s1["total"] == 9.5
    assert s1["kill_to_detect"] == 1.5
    assert s1["total_from_kill"] == 11.0
    # incomplete stage carries launcher phases only
    assert "total" not in stages[1]


def test_launcher_half_only(memkv):
    """A resize whose trainer half never landed (job completed first,
    trainer died before its first step) still reports the launcher
    phases — and no fabricated trainer phases or total."""
    put(memkv, "jp", "s1", "launcher", "podA",
        {"detect": 1.0, "killed": 2.0, "barrier": 2.5, "spawn": 3.0})
    (s,) = summarize_recovery(memkv, "jp")
    assert s["detect_to_kill"] == 1.0
    assert s["kill_to_barrier"] == 0.5
    assert s["barrier_to_spawn"] == 0.5
    for key in ("spawn_to_restored", "restored_to_first_step", "total",
                "total_from_kill"):
        assert key not in s
    # kill_time only decorates COMPLETE records
    (s,) = summarize_recovery(memkv, "jp", kill_time=0.5)
    assert "kill_to_detect" not in s and "total_from_kill" not in s


def test_trainer_half_only_is_skipped(memkv):
    """A trainer half with no launcher half has no detect anchor: the
    summary skips the stage (no crash, no partial garbage) while the
    raw record stays loadable for debugging."""
    put(memkv, "jt", "s1", "trainer", "podA",
        {"restored": 5.0, "first_step": 6.0})
    assert summarize_recovery(memkv, "jt") == []
    recs = load_recovery_records(memkv, "jt")
    assert recs["s1"]["trainer"]["podA"]["first_step"] == 6.0


def test_mixed_partial_and_complete_stages(memkv):
    """One complete stage + one trainer-only stage: the complete stage
    summarizes normally; the orphan half can't corrupt the merge."""
    put(memkv, "jm", "s1", "launcher", "podA",
        {"detect": 10.0, "killed": 11.0, "barrier": 11.5, "spawn": 12.0})
    put(memkv, "jm", "s1", "trainer", "podA",
        {"restored": 14.0, "first_step": 15.0})
    put(memkv, "jm", "s2", "trainer", "podA",
        {"restored": 99.0, "first_step": 100.0})
    stages = summarize_recovery(memkv, "jm")
    assert [s["stage"] for s in stages] == ["s1"]
    assert stages[0]["total"] == 5.0


def test_earliest_detector_and_last_finisher_win(memkv):
    t0 = 50.0
    put(memkv, "j2", "s", "launcher", "podB",
        {"detect": t0 + 1, "killed": t0 + 2, "barrier": t0 + 3,
         "spawn": t0 + 4})
    put(memkv, "j2", "s", "launcher", "podA",  # detected FIRST
        {"detect": t0, "killed": t0 + 1, "barrier": t0 + 3, "spawn": t0 + 4})
    put(memkv, "j2", "s", "trainer", "podA",
        {"restored": t0 + 6, "first_step": t0 + 7})
    put(memkv, "j2", "s", "trainer", "podB",  # finished LAST
        {"restored": t0 + 6, "first_step": t0 + 9})
    s = summarize_recovery(memkv, "j2")[0]
    assert s["detect_at"] == t0
    assert s["total"] == 9.0  # earliest detect -> last first_step


def test_trainer_half_carries_what_it_built(memkv):
    """The trainer's half says how much of restored_to_first_step was
    tracing and lowering, how much compiling, and whether the compile
    cache hit (the program-build ledger's totals, differenced)."""
    from edl_tpu.cluster.recovery import (BUILD_FIELDS, build_fields,
                                          write_trainer_half)
    from edl_tpu.obs.dump import render_report
    before = {"build/train/step/trace_s": 1.0, "build/train/step/lower_s": 0.5,
              "build/train/step/compile_s": 2.0,
              "build/train/step/cache_hits": 1, "build/train/step/builds": 1}
    after = {"build/train/step/trace_s": 1.5, "build/train/step/lower_s": 1.0,
             "build/train/step/compile_s": 6.0,
             "build/train/step/cache_hits": 1,
             "build/train/step/cache_misses": 2,
             "build/train/step/builds": 3,
             # a row that did not exist at ``restored``
             "build/other/add/lower_s": 0.25, "build/other/add/run_s": 9.0}
    built = build_fields(before, after)
    assert built == {"build_trace_lower_s": 1.25, "build_compile_s": 4.0,
                     "build_cache_hits": 0, "build_cache_misses": 2}
    assert tuple(built) == BUILD_FIELDS
    put(memkv, "jb", "s1", "launcher", "podA",
        {"detect": 10.0, "killed": 11.0, "barrier": 11.5, "spawn": 12.0})
    write_trainer_half(memkv, "jb", "s1", "podA", restored=14.0,
                       first_step=20.0, restore_source="peer", builds=built)
    (s,) = summarize_recovery(memkv, "jb")
    assert s["restored_to_first_step"] == 6.0
    assert {f: s[f] for f in BUILD_FIELDS} == built
    text = render_report({"job": dict.fromkeys(
        ("job_id", "job_status", "stage", "pods_running", "cluster_pods",
         "live_pods", "world_size", "train_status", "resizes"), 0),
        "resizes": [s]})
    assert "trace+lower 1.250s  compile 4.000s" in text
    assert "0 hit(s) / 2 miss(es)" in text


def test_a_trainer_half_without_build_fields_still_merges(memkv):
    """An older trainer's half has no ``build_*`` fields: the record
    merges as before and the summary simply lacks them."""
    from edl_tpu.cluster.recovery import BUILD_FIELDS, write_trainer_half
    put(memkv, "jo", "s1", "launcher", "podA",
        {"detect": 10.0, "killed": 11.0, "barrier": 11.5, "spawn": 12.0})
    put(memkv, "jo", "s1", "trainer", "podA",
        {"restored": 14.0, "first_step": 15.0})
    # and a newer one beside it that finished first
    write_trainer_half(memkv, "jo", "s1", "podB", restored=13.0,
                       first_step=14.5, builds={
                           "build_trace_lower_s": 0.1, "build_compile_s": 0.2,
                           "build_cache_hits": 3, "build_cache_misses": 0})
    (s,) = summarize_recovery(memkv, "jo")
    assert s["restored_to_first_step"] == 1.0 and s["total"] == 5.0
    assert not [f for f in BUILD_FIELDS if f in s]
