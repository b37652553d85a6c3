"""LM generation service: KV-cache decoding behind the teacher wire.

The serving half of the LM workload — the reference only ever served
classification-style teachers (Paddle Serving, README.md:51-64); here
the same TPU serving stack (TeacherServer: EDL1 RPC, pad-to-bucket,
request coalescing, TTL-leased discovery registration) hosts
:func:`edl_tpu.models.generate.generate`.  Clients send
``feed={"ids": [B, P] int32}`` and fetch ``["tokens"]`` →
``[B, max_new_tokens]`` continuations.  Every prompt in a request must
genuinely be P tokens long — do NOT right-pad shorter prompts (the
model would condition on the pad tokens and decode from the position
after them); send ragged prompts as separate requests, the server's
coalescing shares forward passes between same-shape requests anyway.
Each distinct (bucket, P) shape compiles once.

Serve a trained checkpoint::

    python examples/lm/serve_lm.py --coord_endpoints host:2379 \
        --service lm --checkpoint_dir /ckpt/lm --layers 12 --embed 768 \
        --max_new_tokens 64 --temperature 0.8 --top_k 40

Query (see ``request()`` below, or any TeacherClient)::

    from examples.lm.serve_lm import request
    toks = request("host:port", np.array([[5, 3, 9]], np.int32))
"""

from __future__ import annotations

import argparse
import dataclasses
import signal
import threading

import numpy as np


def request(endpoint: str, prompts: np.ndarray, timeout: float = 120.0):
    """One-shot client: ``[B, P]`` int32 prompts → generated tokens."""
    from edl_tpu.distill.predict_client import TeacherClient

    client = TeacherClient(endpoint, fetch=["tokens"], timeout=timeout)
    try:
        return client.predict({"ids": prompts.astype(np.int32)})["tokens"]
    finally:
        client.close()


def build_predict_fn(cfg, params, max_new_tokens: int, temperature: float,
                     top_k: int, top_p: float = 0.0, mesh=None):
    """jitted (params, ids, rng) -> tokens, with a fresh fold per call
    so temperature sampling differs between identical requests.

    The returned fn carries a ``stats()`` attribute: for MoE configs it
    reports cumulative ``moe_prefill_drops`` (capacity-overflow on
    prompt passes — an under-provisioned capacity_factor silently
    degrades long prompts; here it's a counter the TeacherServer stats
    RPC exposes)."""
    import jax

    from edl_tpu.models.generate import generate, shard_split_params

    moe = bool(cfg.moe_experts)
    if mesh is not None:
        # tp-sharded serving: params split + device_put by logical
        # axes; the jitted generate follows the data and XLA inserts
        # the tp collectives (tokens match the replicated run exactly
        # — tests/test_generate_sharded.py)
        params = shard_split_params(params, mesh, cfg.num_layers)
        # the model sees its mesh: sharded slabs decode on the einsums
        cfg = dataclasses.replace(cfg, mesh=mesh)

    @jax.jit
    def gen(p, ids, rng):
        return generate(cfg, p, ids, max_new_tokens, rng=rng,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        return_drops=moe)

    counter = {"n": 0, "drops": 0}
    lock = threading.Lock()

    def predict(feed: dict) -> dict:
        with lock:
            counter["n"] += 1
            n = counter["n"]
        rng = jax.random.fold_in(jax.random.key(20_26), n)
        out = gen(params, feed["ids"].astype(np.int32), rng)
        if moe:
            toks, drops = out
            with lock:
                counter["drops"] += int(drops)
        else:
            toks = out
        return {"tokens": np.asarray(toks)}

    def stats() -> dict:
        with lock:
            return ({"moe_prefill_drops": counter["drops"]} if moe else {})

    predict.stats = stats
    return predict


class _ContinuousServer:
    """TeacherClient-compatible RPC front over a ContinuousBatcher.

    Unlike TeacherServer there is NO single inference thread to queue
    behind: the RPC layer is thread-per-connection, every request
    submits its rows to the engine and blocks on futures, and the
    engine batches across whatever is in flight — requests join and
    leave the running decode batch at token granularity."""

    def __init__(self, engine, max_new_tokens: int, port: int = 0):
        from edl_tpu.distill.predict_client import decode_array, encode_array
        from edl_tpu.rpc.server import RpcServer
        from edl_tpu.utils.network import local_ip

        self._engine = engine
        self._max_new = max_new_tokens

        def predict(feed: dict, fetch: list[str]) -> dict:
            ids = decode_array(feed["ids"])
            if len(ids) == 0:
                return {"out": {"tokens": encode_array(
                    np.zeros((0, 0), np.int32))}}
            futs = [engine.submit(row, self._max_new) for row in ids]
            outs = [f.result() for f in futs]
            width = max(len(o) for o in outs)
            toks = np.full((len(outs), width), -1, np.int32)
            for i, o in enumerate(outs):       # ragged under eos: -1 pad
                toks[i, :len(o)] = o
            return {"out": {"tokens": encode_array(toks)}}

        self._rpc = RpcServer(host="0.0.0.0", port=port)
        self._rpc.register("predict", predict)
        self._rpc.register("ping", lambda: {"pong": True})
        self._rpc.register("stats", engine.stats)
        self._rpc.start()
        self.endpoint = f"{local_ip()}:{self._rpc.port}"
        self._register = None

    def register(self, store, service: str):
        from edl_tpu.coord.register import Register
        from edl_tpu.distill.balance import server_key
        self._register = Register(store, server_key(service, self.endpoint),
                                  self.endpoint.encode())
        return self

    def stop(self) -> None:
        if self._register is not None:
            self._register.stop()
        self._rpc.stop()
        self._engine.stop()


def _continuous_server(cfg, params, args, mesh=None) -> _ContinuousServer:
    from edl_tpu.serving import ContinuousBatcher

    engine = ContinuousBatcher(
        cfg, params, slots=args.continuous,
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        eos_id=None if args.eos_id < 0 else args.eos_id, mesh=mesh)
    return _ContinuousServer(engine, args.max_new_tokens, port=args.port)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--coord_endpoints", default="",
                   help="register under --service when set")
    p.add_argument("--service", default="lm")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--checkpoint_dir", default="",
                   help="restore trained params (else random init — demo)")
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--embed", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv_heads", type=int, default=0,
                   help="must match training (GQA)")
    p.add_argument("--mlp", type=int, default=256)
    p.add_argument("--max_len", type=int, default=512)
    p.add_argument("--moe", type=int, default=0,
                   help="serve an MoE checkpoint: experts per block "
                        "(must match training)")
    p.add_argument("--moe_top_k", type=int, default=2,
                   help="experts combined per token (must match "
                        "training — the param tree cannot catch a "
                        "mismatch)")
    p.add_argument("--max_new_tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=1.0)
    p.add_argument("--top_k", type=int, default=0)
    p.add_argument("--top_p", type=float, default=0.0,
                   help="nucleus sampling mass in (0, 1]; 0 disables")
    p.add_argument("--continuous", type=int, default=0, metavar="SLOTS",
                   help="serve with slot-based continuous batching over "
                        "this many decode lanes (edl_tpu/serving): "
                        "requests join/leave the running batch per "
                        "prompt, no convoy behind the longest "
                        "generation; 0 = batch-at-a-time TeacherServer")
    p.add_argument("--eos_id", type=int, default=-1,
                   help="stop generation at this token (continuous "
                        "mode); -1 disables")
    p.add_argument("--tp", type=int, default=0,
                   help="tensor-parallel serving over this many chips "
                        "(params + KV cache sharded; for models bigger "
                        "than one chip's HBM); 0 = single device")
    args = p.parse_args()

    if args.moe and args.moe_top_k > args.moe:
        raise SystemExit(f"--moe_top_k {args.moe_top_k} cannot exceed "
                         f"--moe {args.moe} experts")

    import jax
    import jax.numpy as jnp

    from edl_tpu.distill.teacher import TeacherServer
    from edl_tpu.models.transformer import TransformerConfig, TransformerLM
    from edl_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = TransformerConfig(
        vocab_size=args.vocab, num_layers=args.layers, embed_dim=args.embed,
        num_heads=args.heads, num_kv_heads=args.kv_heads,
        mlp_dim=args.mlp, max_len=args.max_len,
        moe_experts=args.moe, moe_top_k=args.moe_top_k,
        remat=False, dtype=jnp.bfloat16
        if jax.devices()[0].platform == "tpu" else jnp.float32)
    model = TransformerLM(cfg)

    def init_params():
        return model.init(jax.random.key(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]

    if args.checkpoint_dir:
        # the checkpoint holds train_lm's full TrainState; mirror its
        # optimizer (adamw — hyperparameters don't affect the tree
        # structure) to shape the restore, then keep only the params.
        # All under eval_shape: nothing is materialised before restore.
        import optax

        from edl_tpu.train.checkpoint import CheckpointManager
        from edl_tpu.train.state import TrainState
        skeleton = jax.eval_shape(
            lambda: TrainState.create(init_params(), optax.adamw(1e-3)))
        restored = CheckpointManager(args.checkpoint_dir).restore(skeleton)
        if restored is None:
            raise SystemExit(f"no checkpoint under {args.checkpoint_dir}")
        params = restored[0].params
    else:
        params = init_params()    # random weights: wiring demo only

    mesh = None
    if args.tp > 1:
        from edl_tpu.parallel import MeshSpec, build_mesh
        devs = jax.devices()
        if len(devs) < args.tp:
            raise SystemExit(f"--tp {args.tp} but only {len(devs)} devices")
        mesh = build_mesh(MeshSpec(dp=1, tp=args.tp), devices=devs[:args.tp])

    if args.continuous:
        server = _continuous_server(cfg, params, args, mesh=mesh)
    else:
        predict = build_predict_fn(cfg, params, args.max_new_tokens,
                                   args.temperature, args.top_k, args.top_p,
                                   mesh=mesh)
        server = TeacherServer(predict, port=args.port,
                               extra_stats=predict.stats)
    if args.coord_endpoints:
        from edl_tpu.coord.client import connect
        server.register(connect(args.coord_endpoints), args.service)
    print(f"[serve_lm] serving on {server.endpoint} "
          f"(max_new_tokens={args.max_new_tokens}, "
          f"continuous={args.continuous})", flush=True)

    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop.set())
    signal.signal(signal.SIGINT, lambda *_: stop.set())
    stop.wait()
    server.stop()


if __name__ == "__main__":
    main()
