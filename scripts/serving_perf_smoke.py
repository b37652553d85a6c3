"""CI smoke: the big-model serving fast path, end to end (ISSUE 20).

Three phases, each gating one fast-path claim with the same greedy
parity contract the base gateway smoke proves:

1. **everything-on replica through a real gateway** — one replica
   PROCESS on a tp=2 virtual CPU mesh with the paged pool sharded over
   it, chunked prefill AND self-draft speculative decoding enabled,
   fronted by an in-process Gateway.  Mixed traffic (shared-prefix
   shorts, unrelated shorts, a long prompt) must come back
   bit-identical to local ``generate()``, and the replica's /metrics
   page must show the fast path engaged: prefix hits, prefill chunks,
   accepted draft tokens.
2. **chunked-prefill starvation bound** — warm (prefix-reuse) short
   requests admitted while a long prompt prefills: p99 with chunking
   ON must stay within 2x of chunking OFF (chunking bounds the
   per-tick stall a long admission inflicts on live traffic).
3. **speculative decoding** — 100+ prompts through a spec engine,
   every output bit-identical to plain greedy; self-draft accept rate
   > 0.9; tokens/s recorded both ways.

Emits one JSON artifact line (``serving_mesh_tokens_s``,
``serving_prefill_p99_ms``, ``serving_spec_accept_rate``, ...) so the
driver can track the fast path.

Run by scripts/ci.sh:  JAX_PLATFORMS=cpu python scripts/serving_perf_smoke.py
"""

import json
import os
import selectors
import sys
import tempfile
import time
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("EDL_TPU_METRICS_PORT", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

VOCAB, LAYERS, EMBED, HEADS, MLP, MAX_LEN = 53, 2, 32, 2, 64, 128


def _spawn_replica(coord_ep: str, rid: str, metrics_dir: str):
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               EDL_TPU_METRICS_PORT="0", EDL_TPU_METRICS_DIR=metrics_dir,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    proc = subprocess.Popen(
        [sys.executable, "-m", "edl_tpu.serving.replica",
         "--coord_endpoints", coord_ep, "--job_id", "perfsmoke",
         "--replica_id", rid, "--host", "127.0.0.1",
         "--vocab", str(VOCAB), "--layers", str(LAYERS),
         "--embed", str(EMBED), "--heads", str(HEADS), "--mlp", str(MLP),
         "--max_len", str(MAX_LEN), "--slots", "2", "--steps_per_sync", "2",
         "--temperature", "0", "--seed", "0", "--ttl", "2",
         # the whole fast path at once: tp=2 sharded paged pool,
         # chunked prefill, self-draft speculation (draft dims + seed
         # match the target, so acceptance ~1 and parity is strict)
         "--tp", "2", "--kv_block", "4", "--kv_pool_blocks", "96",
         "--prefill_chunk", "32", "--spec_k", "3",
         "--draft_layers", str(LAYERS), "--draft_embed", str(EMBED),
         "--draft_heads", str(HEADS), "--draft_mlp", str(MLP)],
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


def _phase_stack(out: dict) -> None:
    """tp=2 mesh + paged + chunked + spec replica behind a real
    gateway: mixed traffic, bit-exact, fast path visibly engaged."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from edl_tpu.coord.client import CoordClient
    from edl_tpu.coord.server import start_server
    from edl_tpu.gateway import Gateway, GatewayConfig
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
    metrics_dir = tempfile.mkdtemp(prefix="edl-perf-metrics-")
    proc = _spawn_replica(coord_ep, "rep-fast", metrics_dir)
    store = CoordClient(coord_ep)
    gw = Gateway(store, "perfsmoke", GatewayConfig(
        max_inflight=8, max_queue=32, request_timeout_s=300.0,
        wait_slice_s=0.1, poll_period_s=0.1))
    try:
        assert gw.wait_for_replicas(1, 60), "replica never advertised"
        rng = np.random.default_rng(0)
        prefix = rng.integers(1, VOCAB, (12,)).astype(np.int32)
        prompts = [np.concatenate(
            [prefix, rng.integers(1, VOCAB, (n,)).astype(np.int32)])
            for n in (3, 5, 2)]
        prompts += [rng.integers(1, VOCAB, (n,)).astype(np.int32)
                    for n in (4, 6)]
        prompts.append(rng.integers(1, VOCAB, (96,)).astype(np.int32))
        news = [8, 8, 8, 8, 8, 8]

        # lead request first, alone: it commits the shared-prefix
        # chain, so the burst behind it admits through the trie
        t0 = time.monotonic()
        outs = [gw.submit(prompts[0], news[0]).result(timeout=300)]
        futs = [gw.submit(p, n)
                for p, n in zip(prompts[1:], news[1:])]
        outs += [f.result(timeout=300) for f in futs]
        wall = time.monotonic() - t0
        for p, n, o in zip(prompts, news, outs):
            np.testing.assert_array_equal(o, want(p, n))
        out["serving_mesh_tokens_s"] = round(sum(news) / wall, 1)

        # the fast path must have ENGAGED, not just not broken: the
        # replica's /metrics page carries the engine's lifetime stats
        addr_path = os.path.join(metrics_dir,
                                 f"metrics-replica-{proc.pid}.addr")
        deadline = time.time() + 60
        while True:                      # published by the advert loop
            with open(addr_path) as f:
                page = urllib.request.urlopen(
                    f"http://{f.read().strip()}/metrics", timeout=10
                ).read().decode()
            m = parse_exposition(page)
            if m.get(("edl_serving_spec_accepted_total", ()), 0) > 0:
                break
            assert time.time() < deadline, "spec counters never published"
            time.sleep(0.5)
        assert m.get(("edl_serving_kv_prefix_hits", ()), 0) >= 2, \
            "shared-prefix traffic must hit the sharded pool's trie"
        assert m.get(("edl_serving_prefill_chunks_total", ()), 0) >= 2, \
            "the 96-token prompt must have prefilled in chunks"
        assert m.get(("edl_serving_spec_proposed_total", ()), 0) > 0
        rate = (m[("edl_serving_spec_accepted_total", ())]
                / m[("edl_serving_spec_proposed_total", ())])
        assert rate > 0.9, f"self-draft accept rate {rate:.2f}"
        print(f"smoke: tp=2 mesh+paged+chunk+spec replica through the "
              f"gateway — {len(prompts)} mixed requests bit-exact, "
              f"{int(m[('edl_serving_kv_prefix_hits', ())])} prefix hits, "
              f"{int(m[('edl_serving_prefill_chunks_total', ())])} chunks, "
              f"spec accept {rate:.2f}")
    finally:
        gw.close()
        if proc.poll() is None:
            proc.kill()
        store.close()
        coord.stop()


def _phase_chunk_p99(out: dict) -> None:
    """Warm short requests while a long prompt prefills: chunking must
    bound the stall — p99 within 2x of the unchunked engine."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.serving import ContinuousBatcher

    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=LAYERS,
                            embed_dim=EMBED, num_heads=HEADS, mlp_dim=MLP,
                            max_len=256, remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, VOCAB, (12,)).astype(np.int32)
    longs = [rng.integers(1, VOCAB, (224,)).astype(np.int32)
             for _ in range(3)]

    def p99(chunk: int) -> tuple[float, dict]:
        eng = ContinuousBatcher(cfg, params, slots=3, temperature=0.0,
                                steps_per_sync=1, kv_block=4,
                                kv_pool_blocks=256, prefill_buckets=(8, 16),
                                prefill_chunk=chunk)
        try:
            # commit the prefix chain so measured shorts admit via
            # reuse (reuse admissions run every tick, so they see the
            # per-tick stall directly — the thing chunking bounds)
            eng.generate(np.concatenate(
                [prefix, np.asarray([1, 2], np.int32)]), 4, timeout=120)
            # one unmeasured warm short: compiles the reuse-admission
            # jit family so the percentile measures ticks, not XLA
            eng.generate(np.concatenate(
                [prefix, np.asarray([3, 4], np.int32)]), 4, timeout=120)
            lats = []
            for long in longs:
                f_long = eng.submit(long, 2)
                for i in range(6):
                    p = np.concatenate(
                        [prefix,
                         rng.integers(1, VOCAB, (2,)).astype(np.int32)])
                    t0 = time.monotonic()
                    eng.generate(p, 4, timeout=120)
                    lats.append(time.monotonic() - t0)
                f_long.result(timeout=120)
            return float(np.percentile(lats, 99) * 1e3), eng.stats()
        finally:
            eng.stop()

    on_ms, on_stats = p99(32)
    off_ms, off_stats = p99(0)
    assert on_stats["prefill_chunks"] > 0, on_stats
    assert off_stats["prefill_chunks"] == 0, off_stats
    # generous 2x + absolute cushion: the bound protects against the
    # pathological monolithic stall, not CI scheduler jitter
    assert on_ms <= off_ms * 2 + 25, (on_ms, off_ms)
    out["serving_prefill_p99_ms"] = round(on_ms, 1)
    out["serving_prefill_p99_off_ms"] = round(off_ms, 1)
    print(f"smoke: warm-short p99 with a long admission in flight — "
          f"{on_ms:.1f} ms chunked vs {off_ms:.1f} ms monolithic "
          f"({on_stats['prefill_chunks']} chunks)")


def _phase_spec(out: dict) -> None:
    """100+ prompts, spec on vs off: bit-identical everywhere, accept
    rate ~1 on the self-draft, tokens/s recorded both ways."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from edl_tpu.models.generate import generate
    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.serving import ContinuousBatcher

    cfg = TransformerConfig(vocab_size=VOCAB, num_layers=LAYERS,
                            embed_dim=EMBED, num_heads=HEADS, mlp_dim=MLP,
                            max_len=64, remat=False, dtype=jnp.float32)
    params = TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, VOCAB, (int(n),)).astype(np.int32)
               for n in rng.integers(3, 12, (104,))]

    def run(**kw):
        eng = ContinuousBatcher(cfg, params, slots=4, temperature=0.0,
                                steps_per_sync=2, kv_block=0,
                                prefill_buckets=(8, 16), **kw)
        try:
            t0 = time.monotonic()
            futs = [eng.submit(p, 8) for p in prompts]
            outs = [f.result(120) for f in futs]
            return outs, 8 * len(prompts) / (time.monotonic() - t0), \
                eng.stats()
        finally:
            eng.stop()

    spec_outs, spec_tps, spec_stats = run(spec_k=3, draft_cfg=cfg,
                                          draft_params=params)
    plain_outs, plain_tps, _ = run()
    for p, a, b in zip(prompts, spec_outs, plain_outs):
        np.testing.assert_array_equal(a, b)
        want = np.asarray(generate(cfg, params, jnp.asarray(p[None]), 8,
                                   temperature=0.0))[0]
        np.testing.assert_array_equal(a, want)
    assert spec_stats["spec_accept_rate"] > 0.9, spec_stats
    out["serving_spec_accept_rate"] = spec_stats["spec_accept_rate"]
    out["serving_spec_tokens_s"] = round(spec_tps, 1)
    out["serving_nospec_tokens_s"] = round(plain_tps, 1)
    print(f"smoke: {len(prompts)} prompts bit-identical spec vs plain "
          f"(accept {spec_stats['spec_accept_rate']}, "
          f"{spec_tps:.0f} vs {plain_tps:.0f} tok/s on the toy model)")


def main() -> None:
    out: dict = {}
    _phase_stack(out)
    _phase_chunk_p99(out)
    _phase_spec(out)
    print(json.dumps(out))
    print("serving perf smoke OK")


if __name__ == "__main__":
    main()
