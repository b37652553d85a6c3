"""CI smoke: the paged KV cache's three contracts, end to end.

1. **bit-exactness** — the same mixed workload (shared prefixes,
   divergent sessions, unrelated prompts) through a paged engine and an
   unpaged engine yields byte-identical greedy outputs;
2. **throughput** — the heavy-prefix microbench must show prefix-hit
   tokens/s >= cold tokens/s with a prefill-skipped fraction > 0.5, and
   the drain handoff must produce a migration-latency number;
3. **SIGTERM-drain under sustained sessions** — two REAL replica
   processes (``edl-replica --kv_block``) behind an in-process Gateway:
   a session's turn lands on its ring owner, the owner is SIGTERMed
   under load, every accepted request still completes, the session's
   KV chain migrates to the survivor (pin advert published), and the
   session's next turn resumes THERE without re-prefilling (the
   survivor's ``edl_serving_kv_prefill_tokens_skipped`` moves).

Run by scripts/ci.sh:  JAX_PLATFORMS=cpu python scripts/kv_cache_smoke.py
"""

import json
import os
import selectors
import signal
import sys
import tempfile
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("EDL_TPU_METRICS_PORT", "0")
os.environ.setdefault("EDL_TPU_TRACE_DIR",
                      tempfile.mkdtemp(prefix="edl-kv-trace-"))

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB, LAYERS, EMBED, HEADS, MLP, MAX_LEN = 53, 1, 32, 2, 64, 64


def _spawn_replica(coord_ep: str, rid: str, metrics_dir: str):
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               EDL_TPU_METRICS_PORT="0", EDL_TPU_METRICS_DIR=metrics_dir)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.serving.replica",
         "--coord_endpoints", coord_ep, "--job_id", "kvsmoke",
         "--replica_id", rid, "--host", "127.0.0.1",
         "--vocab", str(VOCAB), "--layers", str(LAYERS),
         "--embed", str(EMBED), "--heads", str(HEADS), "--mlp", str(MLP),
         "--max_len", str(MAX_LEN), "--slots", "2", "--steps_per_sync", "4",
         "--temperature", "0", "--seed", "0", "--ttl", "3",
         "--kv_block", "4", "--kv_pool_blocks", "64"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    sel = selectors.DefaultSelector()
    sel.register(proc.stdout, selectors.EVENT_READ)
    deadline = time.time() + 300
    while time.time() < deadline:
        if not sel.select(timeout=1.0):
            if proc.poll() is not None:
                raise AssertionError(f"replica {rid} died silently")
            continue
        line = proc.stdout.readline()
        if "serving on" in line:
            return proc
        if not line and proc.poll() is not None:
            raise AssertionError(f"replica {rid} died before announcing")
    raise AssertionError(f"replica {rid} never announced")


def _parity_section() -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.serving import ContinuousBatcher

    cfg = TransformerConfig(vocab_size=97, num_layers=2, embed_dim=32,
                            num_heads=4, mlp_dim=64, max_len=64,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(5)
    shared = rng.integers(1, 97, (11,)).astype(np.int32)
    work = []     # (prompt, max_new, session)
    for i, n in enumerate((3, 6, 2, 4)):
        tail = rng.integers(1, 97, (n,)).astype(np.int32)
        work.append((np.concatenate([shared, tail]), 6, f"s{i % 2}"))
    work.append((rng.integers(1, 97, (7,)).astype(np.int32), 8, None))

    def run(kv_block: int):
        eng = ContinuousBatcher(cfg, params, slots=2, temperature=0.0,
                                prefill_buckets=(8, 16), steps_per_sync=4,
                                kv_block=kv_block, kv_pool_blocks=64)
        try:
            outs = [eng.generate(p, n, timeout=300) if s is None else
                    eng.submit(p, n, session=s).result(300)
                    for p, n, s in work]
            # second turns per session, extending divergent lines
            convs = {}
            for (p, _n, s), o in zip(work, outs):
                if s is not None and s not in convs:
                    convs[s] = np.concatenate(
                        [p, o, np.asarray([1, 9], np.int32)])
            outs += [eng.submit(convs[s], 5, session=s).result(300)
                     for s in sorted(convs)]
            return outs, eng.stats()
        finally:
            eng.stop()

    paged, stats = run(kv_block=4)
    unpaged, _ = run(kv_block=0)
    assert len(paged) == len(unpaged)
    for a, b in zip(paged, unpaged):
        np.testing.assert_array_equal(a, b)
    assert stats["kv_prefix_hits"] > 0, stats
    print(f"smoke: paged-vs-unpaged greedy parity over {len(paged)} "
          f"generations ({stats['kv_prefix_hits']} prefix hits, "
          f"{stats['kv_prefill_tokens_skipped']} prompt tokens skipped)")


def serving_kv_microbench(n_req: int = 8, prefix_len: int = 160,
                           block: int = 16) -> dict:
    """Prefix-reusable paged KV cache (ISSUE 14): the SAME
    shared-system-prompt workload (one long common prefix, short unique
    tails, short generations — the prefill-dominated regime the cache
    exists for) through an unpaged engine and a paged one whose chain
    is already committed.  Tokens/s counts PROCESSED tokens (prompt +
    generated): identical work either way, so the ratio isolates the
    skipped prefill.  Both paths are pre-compiled outside the measured
    window.  Plus: the wall time of a drain() that migrates one live
    session chain to an adoptive replica (the scale-down warm-handoff
    cost a conversation would otherwise pay as a full re-prefill)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from edl_tpu.coord.memory import MemoryKV
    from edl_tpu.gateway import fleet
    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.serving import ContinuousBatcher
    from edl_tpu.serving.replica import ReplicaServer

    tail_len, new = 8, 2
    cfg = TransformerConfig(vocab_size=61, num_layers=2, embed_dim=16,
                            num_heads=2, mlp_dim=32, max_len=256,
                            remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(23)
    prefix = rng.integers(1, 61, (prefix_len,)).astype(np.int32)

    def prompt(i):
        tail = np.asarray([(7 * i + j) % 60 + 1 for j in range(tail_len)],
                          np.int32)
        return np.concatenate([prefix, tail])

    def run(kv: bool) -> tuple[float, float]:
        eng = ContinuousBatcher(cfg, params, slots=4, temperature=0.0,
                                steps_per_sync=2,
                                kv_block=block if kv else 0)
        try:
            eng.warm(prefix_len + tail_len)    # cold prefill + step jits
            if kv:
                # seed the shared chain, then one unmeasured hit so the
                # reuse-path jit is compiled before the clock starts
                eng.generate(prompt(10_001), new, timeout=600)
                eng.generate(prompt(10_002), new, timeout=600)
            s0 = eng.stats()
            t0 = time.perf_counter()
            futs = [eng.submit(prompt(i), new) for i in range(n_req)]
            for f in futs:
                f.result(timeout=600)
            dt = time.perf_counter() - t0
            s1 = eng.stats()
        finally:
            eng.stop()
        tokens_s = n_req * (prefix_len + tail_len + new) / dt
        did = s1.get("kv_prefill_tokens", 0) - s0.get("kv_prefill_tokens", 0)
        skipped = (s1.get("kv_prefill_tokens_skipped", 0)
                   - s0.get("kv_prefill_tokens_skipped", 0))
        frac = skipped / did if did else 0.0
        return tokens_s, frac

    cold_tokens_s, _ = run(kv=False)
    warm_tokens_s, skipped_frac = run(kv=True)

    # -- session migration: one live chain handed off across a drain --
    store = MemoryKV(sweep_period=1.0)
    servers = []
    migration_ms = None
    try:
        engines = [ContinuousBatcher(cfg, params, slots=2, temperature=0.0,
                                     steps_per_sync=2, kv_block=block)
                   for _ in range(2)]
        servers = [ReplicaServer(store, "benchkv", e,
                                 replica_id=f"kv-{i}", host="127.0.0.1",
                                 ttl=60)
                   for i, e in enumerate(engines)]
        engines[0].submit(prompt(0), new, session="bench-sess").result(600)
        t0 = time.perf_counter()
        servers[0].drain(timeout=60)
        migration_ms = 1e3 * (time.perf_counter() - t0)
        pins = fleet.list_session_pins(store, "benchkv")
        if pins.get("bench-sess") != "kv-1" \
                or engines[1].stats().get("kv_sessions") != 1:
            migration_ms = None          # handoff didn't land: no number
    finally:
        for s in servers:
            s.close()
        store.close()

    out = {
        "serving_cold_tokens_s": round(cold_tokens_s, 1),
        "serving_prefix_tokens_s": round(warm_tokens_s, 1),
        "serving_prefill_skipped_frac": round(skipped_frac, 3),
    }
    if migration_ms is not None:
        out["serving_kv_migration_ms"] = round(migration_ms, 1)
    return out


def _throughput_section() -> dict:
    res = serving_kv_microbench()
    print("smoke: kv microbench ->", json.dumps(res))
    assert res["serving_prefix_tokens_s"] >= res["serving_cold_tokens_s"], \
        f"prefix reuse lost to cold prefill: {res}"
    assert res["serving_prefill_skipped_frac"] > 0.5, res
    assert res.get("serving_kv_migration_ms") is not None, \
        f"drain handoff produced no migration latency: {res}"
    return res


def _sigterm_drain_section() -> None:
    import numpy as np

    import jax
    import jax.numpy as jnp

    from edl_tpu.coord.client import CoordClient
    from edl_tpu.coord.server import start_server
    from edl_tpu.gateway import Gateway, GatewayConfig, fleet
    from edl_tpu.models.generate import generate
    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.obs.metrics import parse_exposition

    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=LAYERS,
                            embed_dim=EMBED, num_heads=HEADS, mlp_dim=MLP,
                            max_len=MAX_LEN, remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(                    # replica --seed 0
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]

    def want(prompt, n):
        return np.asarray(generate(cfg, params, jnp.asarray(prompt[None]),
                                   n, temperature=0.0))[0]

    coord = start_server("127.0.0.1", 0)
    coord_ep = f"127.0.0.1:{coord.port}"
    metrics_dir = tempfile.mkdtemp(prefix="edl-kv-metrics-")
    procs = {rid: _spawn_replica(coord_ep, rid, metrics_dir)
             for rid in ("rep-0", "rep-1")}
    store = CoordClient(coord_ep)
    gw = Gateway(store, "kvsmoke", GatewayConfig(
        max_inflight=8, max_queue=32, request_timeout_s=300.0,
        wait_slice_s=0.1, poll_period_s=0.1, quarantine_s=5.0))
    try:
        assert gw.wait_for_replicas(2, 60), "replicas never advertised"
        rng = np.random.default_rng(1)
        # a session whose ring owner is the replica we will SIGTERM
        sess = next(s for s in (f"conv-{i}" for i in range(1000))
                    if gw._fleet.ring.get_node(s) == "rep-0")
        p1 = rng.integers(1, VOCAB, (9,)).astype(np.int32)
        out1 = gw.generate(p1, 8, session=sess, timeout=300)
        np.testing.assert_array_equal(out1, want(p1, 8))

        # sustained load in flight while the owner drains away
        load = [rng.integers(1, VOCAB,
                             (int(rng.integers(3, 10)),)).astype(np.int32)
                for _ in range(12)]
        futs = [gw.submit(p, 12) for p in load]
        os.kill(procs["rep-0"].pid, signal.SIGTERM)
        outs = [f.result(timeout=300) for f in futs]
        for p, o in zip(load, outs):
            np.testing.assert_array_equal(o, want(p, 12))
        procs["rep-0"].wait(timeout=120)
        print(f"smoke: SIGTERM-drain under load -> all {len(load)} "
              "accepted requests completed")

        # the drain handoff re-pinned the session onto the survivor
        deadline = time.monotonic() + 60
        while fleet.list_session_pins(store, "kvsmoke").get(sess) != "rep-1":
            assert time.monotonic() < deadline, \
                f"session never re-pinned: " \
                f"{fleet.list_session_pins(store, 'kvsmoke')}"
            time.sleep(0.1)
        gw._fleet.refresh()
        assert gw._fleet.session_pin(sess) == "rep-1"

        # next turn resumes WARM on the survivor: bit-exact output and
        # a moving prefill-skipped counter (no re-prefill of the
        # migrated prefix)
        p2 = np.concatenate([p1, out1,
                             rng.integers(1, VOCAB, (2,)).astype(np.int32)])
        out2 = gw.generate(p2, 6, session=sess, timeout=300)
        np.testing.assert_array_equal(out2, want(p2, 6))
        addr_path = os.path.join(
            metrics_dir, f"metrics-replica-{procs['rep-1'].pid}.addr")
        with open(addr_path) as f:
            survivor_metrics = f.read().strip()
        skipped = 0.0
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            page = urllib.request.urlopen(
                f"http://{survivor_metrics}/metrics", timeout=10
            ).read().decode()
            parsed = parse_exposition(page)
            skipped = parsed.get(
                ("edl_serving_kv_prefill_tokens_skipped", ()), 0.0)
            if skipped > 0:
                break
            time.sleep(0.25)     # gauge updates on the advert period
        assert skipped > 0, \
            "migrated session re-prefilled on the survivor"
        assert parsed.get(("edl_serving_kv_sessions", ()), 0) >= 1
        print(f"smoke: session {sess} resumed on rep-1 with {int(skipped)} "
              "prompt tokens skipped (migrated chain, no re-prefill)")
    finally:
        gw.close()
        for proc in procs.values():
            if proc.poll() is None:
                proc.kill()
        store.close()
        coord.stop()


def main() -> None:
    _parity_section()
    _throughput_section()
    _sigterm_drain_section()
    print("kv cache smoke OK")


if __name__ == "__main__":
    main()
