#!/usr/bin/env bash
# CI: install the package and run the suite (the reference's
# scripts/build.sh:67-74 booted an external etcd before ctest; our
# coordination store is in-tree, so the suite is self-contained).
set -euo pipefail
cd "$(dirname "$0")/.."

# air-gapped runners (deps preinstalled) fall back to no-build-isolation
python -m pip install -e ".[image,test]" \
    || python -m pip install -e . --no-deps --no-build-isolation

# static-analysis gate (edl-lint, doc/lint.md): project-aware AST
# checks for the defect classes PRs 6-8 kept re-finding by hand —
# blocking I/O under service locks, lock-order cycles, untyped errors
# on the RPC wire, wall-clock deadlines, untracked threads, knob- and
# metric-catalog drift.  Fails on any NEW finding or any STALE waiver
# against the committed lint_baseline.json (the baseline only ratchets
# down); runs before the test tiers because it is seconds, not minutes
python -m edl_tpu.lint --root .

# fast tier: everything but the multi-process e2e tests
python -m pytest tests/ -q -m "not slow"

# full tier (FULL=1): launcher/jax.distributed end-to-end + the live
# recovery-time measurement (north-star metric)
if [[ "${FULL:-0}" == "1" ]]; then
    python -m pytest tests/ -q -m slow
    python examples/collective/recovery_bench.py
fi

# observability smoke: a few real trainer steps with the /metrics
# endpoint enabled, fetched over HTTP and parsed back — the
# step-latency and resize-phase series must be present, and the dump
# CLI must reproduce summarize_recovery's per-phase totals
JAX_PLATFORMS=cpu python scripts/obs_smoke.py

# memstate smoke: two-pod kill-one-restore-from-peer on the CPU mesh —
# a checkpoint teed into pod A's in-RAM cache + ring-replicated to pod
# B must restore bit-identically from B alone after A dies, and a
# checksum-corrupted replica must fall back to Orbax storage
JAX_PLATFORMS=cpu python scripts/memstate_smoke.py

# gateway smoke: 2 replica processes + gateway on the virtual CPU mesh —
# SIGKILL one under sustained load and every accepted request must still
# complete on the survivor; a saturated gateway must reject (not hang);
# edl_gateway_*/edl_serving_* metrics and route/hedge/retry trace spans
# must be served; a gateway-stamped trace_id must reach a REPLICA
# process's spans and merge into one ordered Perfetto-exportable timeline
JAX_PLATFORMS=cpu python scripts/gateway_smoke.py

# kv cache smoke: the paged KV cache's three contracts — paged-vs-
# unpaged greedy outputs byte-identical over a mixed shared-prefix +
# divergent-session workload; the heavy-prefix microbench gates
# prefix-hit tokens/s >= cold tokens/s with prefill-skipped frac > 0.5
# and a real migration latency; and a SIGTERM-drain of a replica
# PROCESS under sustained sessions loses zero accepted requests while
# >=1 session chain migrates and resumes on the survivor WITHOUT
# re-prefilling (pin advert + moving kv_prefill_tokens_skipped)
JAX_PLATFORMS=cpu python scripts/kv_cache_smoke.py

# chaos smoke: SIGKILL + restart the durable coord server mid-training
# AND mid-serving — WAL replay must restore revision counter, lease
# table and keys bit-exactly; training must resume without
# restore-from-scratch (one trainer start, no membership-changed path);
# zero accepted gateway requests lost; every advert (resource, memstate,
# serving, obs) back within one TTL + restart grace; coord_restart_mttr_s
# recorded; and the EDL_TPU_FAULTS injection harness must fire and be
# healed by the resilient client
JAX_PLATFORMS=cpu python scripts/chaos_smoke.py

# data chaos smoke: durable coord + two elected data-leader candidates
# + three reader pods with faults injected on every data RPC — SIGKILL
# the ACTIVE leader mid-epoch (standby seizes the seat, rebuilds every
# generation from the coord journal, readers reattach; data_leader_mttr_s
# gated) and SIGKILL a producer pod mid-epoch (its files requeue minus
# consumed spans); the exactly-once audit over the raw span logs must
# show zero drops and zero duplicates outside the killed pod's unacked
# tail, with zero reader failures (retries visible in metrics only)
JAX_PLATFORMS=cpu python scripts/data_chaos_smoke.py

# resize smoke: delta-resharding instead of stop-resume — grow-by-one
# and shrink-by-one must complete WITHOUT killing surviving trainer
# processes (same PIDs, exactly one spawn per pod, resize_mode=delta in
# the recovery record, every restore bit-verified against storage), and
# a SIGKILL of the shard-holding leader pod mid-reshard must fall back
# cleanly to the proven stop-resume path and still SUCCEED
JAX_PLATFORMS=cpu python scripts/resize_smoke.py

# delta failover smoke: sub-checkpoint-loss recovery — real launchers +
# durable coord, one pod SIGKILLed mid-delta-interval (sealed chain
# records observably past the committed checkpoint): the job must
# SUCCEED with restore_source=delta, the restore must land at/past the
# freshest sealed step (steps lost <= the delta cadence, not the
# checkpoint interval), and the identical kill with the plane disabled
# must resume AT the checkpoint — badput-per-failure strictly below
# the stop-resume baseline
JAX_PLATFORMS=cpu python scripts/delta_failover_smoke.py

# obs-agg smoke: 2 child processes + parent — one trace_id propagated
# over the EDL1 wire into both children's trace files, the aggregator
# discovers all three via coord-store adverts and serves a merged
# Prometheus-parseable /metrics + /healthz, and edl-obs-dump --merge
# renders one cross-process timeline with valid Perfetto JSON
JAX_PLATFORMS=cpu python scripts/obs_agg_smoke.py

# alerts smoke: the closed observability loop — three trainer child
# processes scraped by a real aggregator background loop running the
# BUILT-IN ruleset (windows scaled): the straggler rule must fire on
# the slow pod, an EDL_TPU_FAULTS-injected stall must fire trainer-hang
# within the rule's window+hold, the incident JSONL record must carry
# the published generation trace_id and land inside that trace's
# edl-obs-dump --merge timeline, and a killed+rebuilt data leader must
# fire the data-leader MTTR rule off the reader's observed outage
JAX_PLATFORMS=cpu python scripts/alerts_smoke.py

# profiling + goodput smoke: the continuous-profiling layer end to
# end — a real instrumented trainer's phase ledger must account for
# >=95% of step wall time and publish live MFU; the aggregator's
# /healthz must carry the goodput block and a resize record must move
# edl_badput_seconds_total{reason="resize"} and nothing else; a
# straggler alert must auto-trigger a profile capture whose manifest
# carries the generation trace id and joins the merged timeline, with
# Perfetto counter tracks alongside the span rows
JAX_PLATFORMS=cpu python scripts/profiling_smoke.py

# remediation smoke: the self-driving cluster loop — three job kinds
# (real launchers + instrumented trainers, a gang distill pod, a
# fake-engine replica fleet behind a real gateway) arbitrated by ONE
# controller, with the alert->action dispatcher armed: a straggler is
# evicted through the preemption-grace path (workerlog + recovery
# record carry reason=straggler-evict), a wedged trainer is healed by
# a TARGETED in-place restart (launcher pid + cluster stage unchanged,
# healthy jobs untouched), a gateway load spike fires reject-burn and
# scales the replica fleet out with zero lost accepted requests,
# serving demand makes training yield a pod (reason=priority-yield)
# and reclaim it on quiet, and the per-job incident logs show every
# alert -> action -> recovery handoff
JAX_PLATFORMS=cpu python scripts/remediation_smoke.py

# postmortem smoke: the black-box flight recorder + bundle loop — an
# induced straggler must AUTOMATICALLY produce a self-contained bundle
# (flight-recorder rings from >=2 processes, TSDB window, coord
# dump_state, workerlog tail, incident record, all joined by the
# generation trace_id on the edl-obs-dump --merge timeline); a
# SIGKILLed aggregator restarted onto the same --history_dir must
# answer windowed rates immediately, resume the goodput observation
# window, and keep the straggler's original firing_since; and
# edl-obs-bundle --incident must reassemble the bundle from the
# durable pieces alone
JAX_PLATFORMS=cpu python scripts/postmortem_smoke.py

# distill chaos smoke: elastic distillation as a production workload
# (ISSUE 18) — real teacher child processes advertised through the
# serving table, a serving spike makes training yield a pod
# (reason=priority-yield in its workerlog) while the teacher floor
# holds, a student stream's backlog record grows the fleet 1->3
# through the controller's arbitration (and fires the distill-backlog
# alert), a teacher SIGKILL mid-epoch costs retries not rows (the
# 800-row stream audits exactly-once, in order), edl_distill_* gauges
# ride the merged /metrics + /healthz, and quiet decays the fleet back
JAX_PLATFORMS=cpu python scripts/distill_chaos_smoke.py

# fleet-sim smoke: the control-plane scale observatory (doc/scale.md)
# at CI-scale decades (N=25/100/400) — a real durable coord server +
# real aggregator under N pod actors; gates: watch-based membership
# propagation stays flat (<2x smallest->largest N) while poll-based
# propagation visibly grows, the scrape cycle stays bounded at the
# largest N, ZERO coord op failures, and the report renderer parses
# its own SIM artifact with growth exponents
JAX_PLATFORMS=cpu python scripts/fleet_sim_smoke.py

# transfer smoke: the streaming data plane's microbench (loopback,
# small payload, subprocess holders) — pipelined/striped fetch must not
# regress below the serial baseline, and the MiB/s numbers land in the
# CI log so throughput trends are visible per run
JAX_PLATFORMS=cpu python scripts/transfer_smoke.py

# data throughput smoke: streamed batch delivery (framed
# get_batch_stream groups + multi-worker prefetch) must not lose to
# the legacy per-batch request/reply consumer under a modeled wire
# RTT, and every epoch in the section — including the one that stops
# a producer mid-epoch — must audit exactly-once
JAX_PLATFORMS=cpu python scripts/data_throughput_smoke.py

# serving perf smoke: the big-model fast path — a tp=2 CPU-mesh
# replica with the sharded paged pool + chunked prefill + self-draft
# speculation behind a real gateway (mixed traffic, bit-exact), the
# chunked starvation bound (warm-short p99 within 2x of monolithic),
# and 100+ prompts bit-identical spec vs plain greedy
JAX_PLATFORMS=cpu python scripts/serving_perf_smoke.py

# chip_smoke refuses the CPU: every key it prints is a device metric, and
# a CPU run under those names would be read as one.  It must exit
# non-zero here and print nothing that could pass for a result.
# (tests/test_benchmark_entry.py holds benchmarks/run.py to the same.)
if JAX_PLATFORMS=cpu python chip_smoke.py > /tmp/edl-smoke.out 2>/dev/null; then
    echo "chip_smoke.py exited 0 on the CPU"; exit 1
fi
grep -q '"ok"' /tmp/edl-smoke.out && { echo "chip_smoke.py printed a result on the CPU"; exit 1; }
echo "chip_smoke refuses the CPU OK"

# packaging sanity: console scripts resolve
edl-lint --help >/dev/null 2>&1 || { echo "edl-lint missing"; exit 1; }
edl-coord --help >/dev/null 2>&1 || { echo "edl-coord missing"; exit 1; }
edl-launch --help >/dev/null 2>&1 || { echo "edl-launch missing"; exit 1; }
edl-controller --help >/dev/null 2>&1 || { echo "edl-controller missing"; exit 1; }
edl-obs-dump --help >/dev/null 2>&1 || { echo "edl-obs-dump missing"; exit 1; }
edl-obs-agg --help >/dev/null 2>&1 || { echo "edl-obs-agg missing"; exit 1; }
edl-obs-top --help >/dev/null 2>&1 || { echo "edl-obs-top missing"; exit 1; }
edl-obs-bundle --help >/dev/null 2>&1 || { echo "edl-obs-bundle missing"; exit 1; }
edl-gateway --help >/dev/null 2>&1 || { echo "edl-gateway missing"; exit 1; }
edl-replica --help >/dev/null 2>&1 || { echo "edl-replica missing"; exit 1; }

# doc drift: every CLI the operator guide teaches must exist
for cmd in edl-coord edl-launch edl-controller edl-discovery \
           edl-obs-dump edl-obs-agg edl-obs-top edl-obs-bundle \
           edl-gateway edl-replica edl-lint; do
    grep -q "$cmd" doc/usage.md || { echo "doc/usage.md missing $cmd"; exit 1; }
done
for f in examples/lm/serve_lm.py examples/collective/collector.py \
         examples/collective/recovery_bench.py \
         examples/collective/imagenet_to_recordio.py \
         examples/collective/decode_bench.py; do
    [[ -f "$f" ]] || { echo "missing $f"; exit 1; }
    grep -q "$(basename "$f")" doc/usage.md \
        || { echo "doc/usage.md missing $(basename "$f")"; exit 1; }
done
echo "CI OK"
