"""The serving cells: gateway -> replica -> ContinuousBatcher -> PagedKVCache.

This process holds the chip and is the one under test: the engine, a
``ReplicaServer`` and a ``GatewayServer`` built through their
constructors with the configuration file's values (the ``edl-replica``
CLI has no flag for KV heads and pins float32), leased into a real
coordination server (a child that never imports JAX).  The load
generator is a second child that never imports JAX either
(``benchmarks/loadgen.py``) and talks to the gateway over the wire.

Set-up: weights on the device from the seed, ``engine.warm()`` for the
prompt buckets this cell's traffic uses, one request through every
chunked-prefill shape it uses, the correctness probes, then warm
traffic from the same trace.  The window opens at the instant the
client fixes (``t0``); counters are differenced at its edges.
"""

from __future__ import annotations

import importlib
import json
import os
import subprocess
import threading
import time

import numpy as np

from . import common

TRACE_AFTER_S = 2.0     # traced run: seconds of window before the trace
TRACE_SECONDS = 4.0
SAMPLE_PERIOD_S = 0.1
PROBE_NEW = 16
# Greedy tokens of the served path (bf16 weights, bf16 KV, bf16 matmuls
# with f32 accumulation, f32 softmax) against the float32 reference's
# full forward pass over prompt + answer, teacher-forced on the served
# answer: at every answer position the reference's logit of the served
# token may lie at most this far under the reference's best logit, in
# units of the reference logits' standard deviation at that position.
# With random weights the top two logits are often closer than bf16's
# error, so the argmax may flip between near-ties, but only between
# them: measured on the chip (PERF.md, Findings) the largest shortfall
# is a few hundredths of a standard deviation.  A wrong cache read, a
# missing position or int8/fp8 arithmetic puts the served token
# whole standard deviations down (a random token is about 4 down).
MARGIN_TOLERANCE_SIGMA = 0.25


class _Spans:
    """Host-side spans the benchmark records around the calls into the
    gateway and the engine, joined with the client's records by the
    prompt's hash (loadgen.prompt_key)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.engine: dict[str, list[float]] = {}

    def tap_engine(self, engine) -> None:
        from loadgen import prompt_key
        submit = engine.submit

        def tapped(prompt, max_new_tokens, *a, **kw):
            key = prompt_key(np.asarray(prompt, np.int32).reshape(-1)
                             .tolist())
            t0 = time.monotonic()
            fut = submit(prompt, max_new_tokens, *a, **kw)

            def done(_f, key=key, t0=t0):
                with self.lock:
                    self.engine[key] = [t0, time.monotonic()]
            fut.add_done_callback(done)
            return fut

        engine.submit = tapped


def _bucket(n: int, max_len: int) -> int:
    """The engine's prompt-length bucket rule (engine.py: the default
    buckets, extended by doubling up to the cache length)."""
    from edl_tpu.serving.engine import DEFAULT_PREFILL_BUCKETS
    buckets = sorted(b for b in DEFAULT_PREFILL_BUCKETS if b <= max_len)
    while buckets[-1] < max_len:
        buckets.append(min(buckets[-1] * 2, max_len))
    return next(b for b in buckets if b >= n)


def _buckets_for(lens: list[int], chunk: int, max_len: int) -> tuple[set, set]:
    """(monolithic prefill buckets, chunked final buckets) that prompts
    of these lengths reach."""
    mono, final = set(), set()
    for n in lens:
        if chunk and n > chunk:
            off = chunk * ((n - 1) // chunk)
            final.add(_bucket(n - off, max_len))
        else:
            mono.add(_bucket(n, max_len))
    return mono, final


def _warm_commits(engine, counts: list[int]) -> None:
    """``engine.warm()`` leaves out the pool-commit program, which
    ``PagedKVCache`` compiles once per number of blocks a finished
    request commits: tens of compiles in the middle of traffic.  Before
    any request the pool is empty, so writing blocks 0..n-1 of slot 0's
    (zeroed) slab into it changes nothing.  This reaches into the
    engine (``_kv``, ``_cache``): PERF.md lists it for the PR that
    teaches ``warm()`` the commit sizes; without those attributes the
    commits compile in the window and ``serve_compiles_in_window``
    says so."""
    kv = getattr(engine, "_kv", None)
    if kv is None or not hasattr(engine, "_cache"):
        print("[bench] no engine._kv/_cache: pool commits not warmed",
              flush=True)
        return
    # the engine hands slot and length lists of each admission-group
    # size to jnp.asarray: one tiny program per list length
    import jax.numpy as jnp
    for k in getattr(engine, "PREFILL_KS", ()):
        jnp.asarray(list(range(k)), jnp.int32).block_until_ready()
    for n in counts:
        engine.run_on_engine(
            lambda n=n: kv.store_blocks(engine._cache, 0, 0, list(range(n))),
            timeout=600.0)


def _compile_events() -> list[tuple[float, str]]:
    import jax
    times: list[tuple[float, str]] = []

    def listen(name, _dur, **kw):
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            times.append((time.monotonic(), str(kw.get("fun_name", "?"))))

    jax.monitoring.register_event_duration_secs_listener(listen)
    return times


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    children = common.Children()
    try:
        return _run(cell, conf, traffic, args, t_start, children)
    finally:
        children.stop()


def _run(cell, conf, traffic, args, t_start, children) -> dict:
    import model
    import reference
    import stats as bstats
    rc = conf["run"]
    gen = importlib.import_module(f"generators.{traffic['generator']}")
    plan = gen.schedule(traffic, args.seed, float(args.seconds),
                        conf["vocab_size"])
    shapes = gen.shapes(traffic, float(args.seconds), rc["kv_block"])
    if shapes["max_total"] > rc["max_len"]:
        raise ValueError(f"the traffic's longest request "
                         f"({shapes['max_total']}) exceeds max_len")
    phases = common.Phases(t_start)
    ep, store = children.coord()
    phases.mark("imports+coord")

    import jax
    import jax.numpy as jnp

    from edl_tpu.gateway.gateway import GatewayConfig, GatewayServer
    from edl_tpu.rpc.client import RpcClient
    from edl_tpu.serving.engine import ContinuousBatcher
    from edl_tpu.serving.replica import ReplicaServer
    from edl_tpu.utils import constants

    compiles = _compile_events()
    cfg = model.transformer_config(conf, max_len=rc["max_len"], remat=False)
    params = model.init_params(cfg, args.seed, rc["param_dtype"],
                               split_layers=True)
    engine = ContinuousBatcher(
        cfg, params, slots=rc["slots"], temperature=rc["temperature"],
        top_k=0, steps_per_sync=rc["steps_per_sync"],
        kv_block=rc["kv_block"], kv_pool_blocks=rc["kv_pool_blocks"],
        prefix_reuse=bool(constants.KV_REUSE),
        kv_max_sessions=rc["kv_max_sessions"],
        prefill_chunk=rc["prefill_chunk"])
    phases.mark("jax+weights+engine")
    spans = _Spans()
    spans.tap_engine(engine)
    lens = shapes.get("prompt_lens") or (
        shapes["doc_lens"] + [shapes["question_max"]])
    probe_len = traffic["probe_tokens"]
    mono, final = _buckets_for(lens + [probe_len], rc["prefill_chunk"],
                               rc["max_len"])
    if traffic["loop"] == "closed":
        # later turns: a suffix after the pooled prefix, up to one block
        # + a question + an answer not yet committed
        mono |= {_bucket(n, rc["max_len"]) for n in
                 (32, shapes["question_max"] + rc["kv_block"])}
    t_w = time.monotonic()
    for b in sorted(mono):
        engine.warm(b)
    phases.mark("engine.warm")
    _warm_commits(engine, shapes["commit_block_counts"])
    phases.mark("pool-commit sizes")
    print(f"[bench] warmed buckets {sorted(mono)} and "
          f"{len(shapes['commit_block_counts'])} pool-commit sizes in "
          f"{time.monotonic() - t_w:.1f}s; chunk-final buckets "
          f"{sorted(final)}", flush=True)

    job_id = f"bench-{cell['name']}"
    replica = ReplicaServer(store, job_id, engine, replica_id="r0",
                            host="127.0.0.1")
    gateway = GatewayServer(store, job_id, GatewayConfig(), host="127.0.0.1")
    if not gateway.gateway.wait_for_replicas(1, 60.0):
        raise RuntimeError("the gateway never saw the replica")
    rng = np.random.default_rng([args.seed % (1 << 63), 5])
    failed_setup = 0
    with RpcClient(gateway.endpoint, 330.0) as gw:
        def ask(prompt, max_new, session=None):
            kw = {} if session is None else {"session": session}
            return gw.call("gate_generate", prompt=prompt, max_new=max_new,
                           timeout=300.0, _timeout=330.0, **kw)["tokens"]

        # one request through every chunked shape the traffic reaches
        # (engine.warm cannot: at these lengths it would also compile
        # and run an 8-lane monolithic prefill of the whole cache)
        C = rc["prefill_chunk"]
        for b in sorted(final):
            n = C + b - 3
            out = ask(rng.integers(1, conf["vocab_size"], n).tolist(), 2)
            failed_setup += len(out) != 2
        # -- correctness probes, outside the window
        probe = rng.integers(1, conf["vocab_size"], probe_len).tolist()
        cold = ask(probe, PROBE_NEW)
        before = engine.stats()
        pooled = ask(probe, PROBE_NEW)      # now its prefix is in the pool
        after = engine.stats()
    phases.mark("chunk shapes+probes")
    hit = (after.get("kv_prefix_hits", 0) - before.get("kv_prefix_hits", 0))
    ids = jnp.asarray([probe + cold], jnp.int32)
    ref = np.asarray(reference.logits(conf, params, ids[:, :-1]))[0]
    at = ref[len(probe) - 1:]               # predicts answer token j
    short = (at.max(-1) - at[np.arange(len(cold)), cold]) / at.std(-1)
    agree = int((at.argmax(-1) == np.asarray(cold)).sum())
    # cold and pooled take different programs (chunked prefill against
    # gather + suffix prefill), so a near-tie may flip between them too:
    # up to the first difference they must be equal, and there the
    # pooled token must itself be within the tolerance of the best
    split = next((i for i, (a, b) in enumerate(zip(cold, pooled))
                  if a != b), None)
    pooled_ok = len(pooled) == len(cold) and (
        split is None or float(
            (at[split].max() - at[split][pooled[split]]) / at[split].std())
        <= MARGIN_TOLERANCE_SIGMA)
    phases.mark("reference")
    print(f"[bench] probe: served {cold} pooled-equal {cold == pooled} "
          f"(first difference at {split}, within tolerance {pooled_ok}) "
          f"prefix-hit {hit}; reference argmax agrees on {agree}/"
          f"{len(cold)}, largest shortfall {short.max():.4f} sigma "
          f"(tolerance {MARGIN_TOLERANCE_SIGMA})", flush=True)
    del ref, at

    # -- the client
    job = {"endpoint": gateway.endpoint, "seconds": float(args.seconds),
           "traffic": traffic, "plan": plan}
    client = children.spawn(
        [os.path.join(common.ROOT, "benchmarks", "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    client.stdin.write(json.dumps(job) + "\n")
    client.stdin.flush()
    client.stdin.close()
    first = json.loads(client.stdout.readline() or "{}")
    if first.get("event") != "t0":
        raise RuntimeError(f"the load generator did not start: {first}")
    t0 = first["t0"]
    time.sleep(max(0.0, t0 - time.monotonic()))
    setup_s = t0 - t_start
    phases.mark("client start+warm traffic")
    print(phases.line(), flush=True)
    c0 = engine.stats()
    n_compiles0 = len(compiles)
    samples: list[tuple[int, int]] = []
    halt = threading.Event()

    def sampler():
        while not halt.wait(SAMPLE_PERIOD_S):
            s = engine.stats()
            samples.append((s["queue_depth"], s["active_slots"]))

    th = threading.Thread(target=sampler, daemon=True)
    th.start()
    trace = None
    if args.trace:
        trace = common.TraceWindow(os.path.join(children.tmp, "trace"))
        time.sleep(max(0.0, t0 + TRACE_AFTER_S - time.monotonic()))
        trace.start()
        time.sleep(min(TRACE_SECONDS, max(0.5, args.seconds - TRACE_AFTER_S)))
        trace.stop()
    time.sleep(max(0.0, t0 + args.seconds - time.monotonic()))
    c1 = engine.stats()
    n_compiles1 = len(compiles)
    halt.set()
    th.join(5)
    last = None
    for line in client.stdout:
        msg = json.loads(line)
        if msg["event"] == "done":
            last = msg
    client.wait(60)
    if last is None:
        raise RuntimeError("the load generator ended without its records")
    records = last["records"]
    facts = common.device_facts()
    gateway.stop()
    replica.close()
    engine.stop()

    # -- reduce
    seconds = float(args.seconds)
    if traffic["loop"] == "open":
        judged = [r for r in records if r["window"]]
        rate = bstats.window_token_rate(records, t0, seconds)
        lat = bstats.open_latencies(records)
        late = [r["t_send"] - r["t_due"] for r in records
                if r.get("t_send") is not None]
        print(f"[bench] generator lateness: median "
              f"{np.median(late) * 1e3:.2f} ms, max {max(late) * 1e3:.2f} ms "
              f"over {len(late)} requests", flush=True)
        e2e = {"serve_tokens_per_s": rate, "setup_s": setup_s}
        if len(lat) >= 0.9 * len(judged) and lat:
            e2e["serve_latency_p50_s"] = bstats.percentile(lat, 50)
            e2e["serve_latency_p90_s"] = bstats.percentile(lat, 90)
    else:
        t1 = t0 + seconds
        judged = [r for r in records
                  if r["t_send"] >= t0 and r["t_done"] <= t1]
        e2e = {"serve_tokens_per_s":
               bstats.whole_request_rate(records, t0, seconds),
               "setup_s": setup_s}
    failed = sum(not r["ok"] for r in judged) + failed_setup
    overhead = []
    for r in judged:
        sp = spans.engine.get(r.get("key"))
        if r["ok"] and sp:
            base = r["t_due"] if "t_due" in r else r["t_send"]
            overhead.append((r["t_done"] - base) - (sp[1] - sp[0]))
    checks = {
        "probe_tokens": len(cold) == PROBE_NEW and len(pooled) == PROBE_NEW,
        "pooled_equals_cold": pooled_ok and hit >= 1,
        "reference_margin": bool(short.max() <= MARGIN_TOLERANCE_SIGMA),
        "answers_whole": failed == 0 and len(judged) > 0,
        "client_clean": not last.get("stuck_clients"),
    }
    counters = {k: c1[k] - c0[k] for k in c0
                if isinstance(c0[k], (int, float))
                and not isinstance(c0[k], bool)}
    counters.update(
        window_s=seconds, slots=rc["slots"],
        steps_per_sync=rc["steps_per_sync"],
        compiles_in_window=n_compiles1 - n_compiles0,
        queue_depth_samples=[s[0] for s in samples],
        active_slots_samples=[s[1] for s in samples],
        prompt_tokens_submitted=sum(
            r["n_prompt"] for r in records if r.get("t_send") is not None
            and t0 <= r["t_send"] < t0 + seconds),
        gateway_overheads_s=overhead,
        mean_context_tokens=float(np.mean(
            [r["n_prompt"] + r["max_new"] / 2 for r in judged] or [0])))
    print(f"[bench] window: {len(judged)} requests judged, {failed} failed, "
          f"checks {checks}, compiles in window "
          f"{[n for _t, n in compiles[n_compiles0:n_compiles1]]}",
          flush=True)
    return {
        "correct": all(checks.values()), "attempted": len(judged),
        "failed": failed, "end_to_end": e2e, "device": facts,
        "counters": counters, "records": judged,
        "trace": trace.reduce() if trace else None,
        "trace_span_s": (trace.t_stop - trace.t_start) if trace else None,
    }
