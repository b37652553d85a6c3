"""Elastic LM pretraining: the beyond-parity parallelism workload.

The reference had nothing past data parallelism (SURVEY.md §5
"Long-context / sequence parallelism: absent"); this example is the
target-config capability delivered TPU-natively: a TransformerLM
trained over a dp × sp × tp mesh — parameters sharded by the logical
rules (embed on fsdp, mlp/heads on tp), tokens sharded over batch AND
sequence, attention dispatched to the pallas flash kernel on TPU (or
ring attention across sp with ``--attention ring``) — under the same
elastic launcher, checkpoints and stop-resume as every other workload::

    python -m edl_tpu.collective.launch --job_id lm --nodes_range 1:8 \
        --checkpoint_dir /ckpt/lm examples/lm/train_lm.py -- \
        --layers 12 --embed 768 --seq_len 1024 --tp 4

The synthetic corpus is an order-k Markov chain over the vocab, so the
model has real sequence structure to learn: per-token loss must drop
well below the unigram entropy for the run to count.
"""

from __future__ import annotations

import argparse
import json
import os


def parse_args():
    p = argparse.ArgumentParser()
    p.add_argument("--epochs", type=int, default=3)
    p.add_argument("--steps_per_epoch", type=int, default=20)
    p.add_argument("--batch_size", type=int, default=8, help="per host")
    p.add_argument("--seq_len", type=int, default=128)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--embed", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--kv_heads", type=int, default=0,
                   help="grouped-query attention: K/V heads (0 = --heads, "
                        "i.e. MHA); decode cache shrinks by heads/kv_heads")
    p.add_argument("--mlp", type=int, default=256)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--tp", type=int, default=0, help="0 = auto (2 if even)")
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--fsdp", type=int, default=1,
                   help="zero-style parameter sharding axis size")
    p.add_argument("--pp", type=int, default=1,
                   help=">1 pipelines the decoder blocks over the pp mesh "
                        "axis (GPipe over ppermute; composes with "
                        "--tp/--fsdp — not with --sp/ring or --moe)")
    p.add_argument("--pp_microbatches", type=int, default=4)
    p.add_argument("--attention", default="auto",
                   choices=["auto", "dense", "splash", "flash", "ring"])
    p.add_argument("--remat", nargs="?", const="on", default="auto",
                   choices=["auto", "on", "off"],
                   help="rematerialisation; auto = off when the batch "
                        "fits HBM (transformer.auto_layout)")
    p.add_argument("--scan_layers", nargs="?", const="on", default="auto",
                   choices=["auto", "on", "off"],
                   help="lax.scan over stacked layers; auto = unroll "
                        "at <= 16 layers (faster steps, ~1 min compile)")
    p.add_argument("--moe", type=int, default=0,
                   help=">0 replaces each block's FFN with this many "
                        "routed experts, sharded over the ep mesh axis")
    p.add_argument("--moe_top_k", type=int, default=2)
    p.add_argument("--moe_aux_weight", type=float, default=0.01)
    p.add_argument("--ep", type=int, default=1,
                   help="expert-parallel mesh axis size (use with --moe)")
    p.add_argument("--dcn_dp", type=int, default=0,
                   help="data-parallel replica groups across slices (DCN); "
                        "0 = auto (one group per slice)")
    p.add_argument("--fused_ce", action="store_true",
                   help="blockwise fused cross-entropy: never materialise "
                        "the [B, L, vocab] logits (edl_tpu/ops/ce.py)")
    p.add_argument("--ce_block", type=int, default=4096)
    return p.parse_args()


def markov_corpus(args, seed):
    """Order-1 Markov chain with a sparse, peaked transition table —
    learnable sequence structure (unigram entropy >> bigram entropy)."""
    import numpy as np

    rng = np.random.default_rng(7)  # the CHAIN is fixed across hosts
    nxt = rng.integers(0, args.vocab, (args.vocab, 4))  # 4 likely successors

    def batches(epoch_rng):
        ids = np.empty((args.batch_size, args.seq_len + 1), np.int32)
        for b in range(args.batch_size):
            t = int(epoch_rng.integers(args.vocab))
            for i in range(args.seq_len + 1):
                ids[b, i] = t
                if epoch_rng.random() < 0.9:  # peaked transitions
                    t = int(nxt[t, epoch_rng.integers(4)])
                else:
                    t = int(epoch_rng.integers(args.vocab))
        return ids

    erng = np.random.default_rng(seed)
    while True:
        yield {"ids": batches(erng)}


class _PipelinedLM:
    """TransformerLM with its decoder blocks pipelined over the pp mesh
    axis — same submodules (Embed / Block / RMSNorm / head), but the
    stacked block params are fed through
    :func:`edl_tpu.ops.pipeline.pipeline_apply` instead of ``nn.scan``,
    so each pp shard holds and computes only its stage's layers.
    Module-shaped adapter: ``init``/``apply`` like flax; ``mesh`` is
    bound after the trainer builds it."""

    def __init__(self, cfg, n_microbatches: int):
        import flax.linen as nn
        import jax.numpy as jnp

        from edl_tpu.models.transformer import Block, RMSNorm, _remat

        self.cfg = cfg
        self.M = n_microbatches
        self.mesh = None  # bound by main() once the trainer exists
        self.embed = nn.Embed(cfg.vocab_size, cfg.embed_dim,
                              param_dtype=jnp.float32, dtype=cfg.dtype)
        # TransformerLM's own remat policy (what it keeps for the
        # backward pass is decided in one place)
        self.block = (_remat(Block) if cfg.remat else Block)(cfg)
        self.norm = RMSNorm(cfg.dtype)
        self.head = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                             param_dtype=jnp.float32)

    def init(self, key, ids, train: bool = True):
        import jax
        import jax.numpy as jnp

        ks = jax.random.split(key, self.cfg.num_layers + 3)
        pe = self.embed.init(ks[0], ids)["params"]
        x = self.embed.apply({"params": pe}, ids)
        pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        layers = [self.block.init(ks[1 + i], x, pos)["params"]
                  for i in range(self.cfg.num_layers)]
        stacked = jax.tree.map(lambda *a: jnp.stack(a), *layers)
        return {"params": {"embed": pe, "layers": stacked,
                           "norm": self.norm.init(ks[-2], x)["params"],
                           "head": self.head.init(ks[-1], x)["params"]}}

    def apply(self, variables, ids, train: bool = True):
        import jax.numpy as jnp

        from edl_tpu.ops.pipeline import pipeline_apply

        p = variables["params"]
        x = self.embed.apply({"params": p["embed"]}, ids)

        def stage(pl, h):
            pos = jnp.broadcast_to(jnp.arange(h.shape[1]), h.shape[:2])
            out, _ = self.block.apply({"params": pl}, h, pos)
            return out

        x = pipeline_apply(stage, p["layers"], x, self.mesh,
                           n_microbatches=self.M)
        x = self.norm.apply({"params": p["norm"]}, x)
        return self.head.apply({"params": p["head"]}, x).astype(jnp.float32)

    def logical_axes(self, params_shape):
        """Stage dim of the stacked layers on pp; within each stage the
        block weights keep the transformer's megatron/fsdp axes (the
        pipeline shard_map is manual over pp only, so tp/fsdp stay
        under GSPMD and compose)."""
        import jax

        from edl_tpu.models import transformer as tf_mod
        from edl_tpu.models.logical import logical_axes_from_paths

        repl = jax.tree.map(lambda l: (None,) * l.ndim, params_shape)
        block_axes = logical_axes_from_paths(
            {"layers": params_shape["layers"]}, tf_mod.LOGICAL_RULES)

        def stage_first(axes, leaf):
            if axes is None or all(a is None for a in axes):
                return ("stage",) + (None,) * (leaf.ndim - 1)
            return ("stage",) + tuple(axes[1:])

        def is_axes(x):  # stop tree.map at the axes TUPLES, not inside
            return x is None or (isinstance(x, tuple) and all(
                a is None or isinstance(a, str) for a in x))

        repl["layers"] = jax.tree.map(stage_first, block_axes["layers"],
                                      params_shape["layers"],
                                      is_leaf=is_axes)
        # embed/head follow the unstacked model's layout
        repl["embed"] = jax.tree.map(
            lambda l: ("vocab", "embed") if l.ndim == 2 else (None,) * l.ndim,
            params_shape["embed"])
        repl["head"] = jax.tree.map(
            lambda l: ("embed", "vocab") if l.ndim == 2 else (None,) * l.ndim,
            params_shape["head"])
        return repl


def main() -> None:
    args = parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from edl_tpu.cluster.env import TrainerEnv
    from edl_tpu.models import transformer as tf_mod
    from edl_tpu.models.logical import logical_axes_from_paths
    from edl_tpu.models.transformer import (
        TransformerConfig, TransformerLM, lm_loss,
    )
    from edl_tpu.parallel import MeshSpec
    from edl_tpu.train import ElasticTrainer, TrainConfig
    from edl_tpu.train.distributed import connect_store, initialize_from_env

    tenv = initialize_from_env(TrainerEnv())
    store = connect_store(tenv)
    world, rank = max(1, tenv.world_size), tenv.global_rank

    n_dev = len(jax.devices())
    if args.pp > 1:
        if args.sp > 1 or args.attention == "ring":
            raise SystemExit("--pp cannot combine with --sp/--attention "
                             "ring: ring applies its own shard_map over "
                             "sp and nesting it inside the pipeline's "
                             "manual-over-pp shard_map fails jax's nested "
                             "axis checks; use auto/dense/flash")
        if args.layers % args.pp:
            raise SystemExit(f"--layers {args.layers} must divide evenly "
                             f"over --pp {args.pp} stages")
        # pp composes with tp/fsdp: the pipeline shard_map is manual
        # over pp only, everything else stays under GSPMD
        free = max(1, n_dev // (args.pp * args.fsdp))
        tp = args.tp or (2 if free % 2 == 0 else 1)
        sp = 1
        spec = MeshSpec(dp=-1, pp=args.pp, tp=tp, fsdp=args.fsdp,
                        dcn_dp=args.dcn_dp)
        # microbatches must divide the GLOBAL batch (the pipeline body
        # sees the global microbatch; GSPMD splits it over dp/fsdp);
        # clamp to the largest divisor <= requested
        m = min(args.pp_microbatches, args.batch_size)
        while args.batch_size % m:
            m -= 1
        if m != args.pp_microbatches:
            print(f"[train_lm] pp_microbatches clamped {args.pp_microbatches}"
                  f" -> {m} (global batch {args.batch_size})", flush=True)
        args.pp_microbatches = m
    else:
        if args.fsdp < 1 or args.sp < 1 or args.ep < 1:
            raise SystemExit("--fsdp, --sp and --ep must be >= 1")
        if args.ep > 1 and not args.moe:
            raise SystemExit("--ep needs --moe (no expert weights to shard)")
        # auto-tp from the devices LEFT once fsdp/sp/ep take their share
        free = max(1, n_dev // (args.fsdp * args.sp * args.ep))
        tp = args.tp or (2 if free % 2 == 0 else 1)
        sp = args.sp
        spec = MeshSpec(dp=-1, fsdp=args.fsdp, tp=tp, sp=sp, ep=args.ep,
                        dcn_dp=args.dcn_dp)

    if args.moe and args.pp > 1:
        raise SystemExit("--moe is not supported by the --pp adapter")
    if args.moe and args.moe_top_k > args.moe:
        raise SystemExit(f"--moe_top_k {args.moe_top_k} cannot exceed "
                         f"--moe {args.moe} experts")
    cfg = TransformerConfig(vocab_size=args.vocab, num_layers=args.layers,
                            embed_dim=args.embed, num_heads=args.heads,
                            num_kv_heads=args.kv_heads,
                            mlp_dim=args.mlp, max_len=args.seq_len,
                            attention_impl=args.attention,
                            moe_experts=args.moe, moe_top_k=args.moe_top_k,
                            dtype=jnp.bfloat16 if
                            jax.devices()[0].platform == "tpu"
                            else jnp.float32)
    # layout knobs default to the product's automatic choice (unroll
    # shallow stacks, remat only when the batch doesn't fit HBM) so the
    # shipped defaults ARE the fast configuration; explicit on/off wins
    import dataclasses as _dc

    from edl_tpu.models.transformer import auto_layout
    # the batch splits over dp x fsdp ONLY — dividing by all local
    # devices would under-estimate activations 8x on a tp=8 mesh
    sizes = spec.resolve(len(jax.devices()))
    batch_ways = max(1, sizes["dp"] * sizes["fsdp"])
    global_bs = args.batch_size * max(1, jax.process_count())
    auto_cfg = auto_layout(cfg, max(1, global_bs // batch_ways),
                           args.seq_len)
    cfg = _dc.replace(
        cfg,
        remat=(auto_cfg.remat if args.remat == "auto"
               else args.remat == "on"),
        scan_layers=(auto_cfg.scan_layers if args.scan_layers == "auto"
                     else args.scan_layers == "on"))
    model = (_PipelinedLM(cfg, args.pp_microbatches) if args.pp > 1
             else TransformerLM(cfg))

    if args.fused_ce and args.pp > 1:
        raise SystemExit("--fused_ce applies to the TransformerLM head; "
                         "the --pp adapter computes its own head")

    def loss_fn(params, extra, batch, rng):
        # TransformerLM returns aux_total=0 for dense configs, so the
        # pp==1 paths always ask for it; only the loss term is gated
        metrics = {}
        if args.fused_ce:
            from edl_tpu.models.transformer import lm_loss_fused
            h, aux = model.apply({"params": params}, batch["ids"][:, :-1],
                                 return_hidden=True, with_aux=True)
            loss = lm_loss_fused(params, h, batch["ids"][:, 1:], cfg,
                                 block_size=args.ce_block)
        elif args.pp > 1:
            logits = model.apply({"params": params}, batch["ids"][:, :-1])
            loss, aux = lm_loss(logits, batch["ids"][:, 1:]), None
        else:
            logits, aux = model.apply({"params": params},
                                      batch["ids"][:, :-1], with_aux=True)
            loss = lm_loss(logits, batch["ids"][:, 1:])
        if args.moe:
            loss = loss + args.moe_aux_weight * aux
            metrics["moe_aux"] = aux
        return loss, (extra, metrics)

    trconf = TrainConfig(mesh_spec=spec, checkpoint_dir=tenv.checkpoint_dir,
                         global_batch_size=args.batch_size * world,
                         log_every=0)
    trainer = ElasticTrainer(loss_fn, trconf, store=store, tenv=tenv)
    if args.pp > 1:
        model.mesh = trainer.mesh
    elif args.attention == "ring" or trainer.mesh.size > 1:
        # ring needs the mesh; the splash kernel uses it to stay on each
        # device's own rows (ops/attention._splash)
        cfg = _dc.replace(cfg, mesh=trainer.mesh)
        model = TransformerLM(cfg)

    from edl_tpu.parallel.mesh import batch_divisor

    def init():
        # init shapes must satisfy the mesh: batch divisible by the data
        # axes, sequence a multiple of sp (the ring shard_map shards both)
        b0 = batch_divisor(trainer.mesh)
        seq0 = sp * max(2, -(-8 // sp))
        ids0 = jnp.zeros((b0, seq0), jnp.int32)
        return model.init(jax.random.key(0), ids0)["params"], None

    params_shape = jax.eval_shape(lambda: init()[0])
    logical = (model.logical_axes(params_shape) if args.pp > 1 else
               logical_axes_from_paths(params_shape, tf_mod.LOGICAL_RULES))
    state, meta = trainer.restore_or_create(init, optax.adamw(args.lr),
                                            param_logical=logical)
    print(f"[train_lm] rank={rank}/{world} mesh={dict(trainer.mesh.shape)} "
          f"attn={args.attention} dtype={jnp.dtype(cfg.dtype).name} "
          f"remat={cfg.remat} resume_epoch={meta.next_epoch}", flush=True)

    def data_fn(epoch: int):
        gen = markov_corpus(args, 1000 * (epoch + 1) + rank)
        for _ in range(args.steps_per_epoch):
            yield next(gen)

    losses = []

    def metric_fn(p, e, b):
        # ONE stable function object: make_eval_step caches the jitted
        # eval graph by metric-fn identity — a fresh lambda per epoch
        # would recompile every time
        logits = model.apply({"params": p}, b["ids"][:, :-1])
        ll = jax.nn.log_softmax(logits.astype(jnp.float32))
        tgt = b["ids"][:, 1:]
        tok = jnp.take_along_axis(ll, tgt[..., None], -1)[..., 0]
        return {"nll": -tok.mean(axis=-1)}  # per-example mean token NLL

    def on_epoch_end(epoch, st, meta_):
        # eval loss on held-out chains from the same process
        gen = markov_corpus(args, 999_000 + epoch)
        val = trainer.evaluate(st, (next(gen) for _ in range(4)), metric_fn)
        losses.append(round(val["nll"], 4))
        print(f"[train_lm] epoch {epoch}: val_nll={val['nll']:.4f}", flush=True)

    state, meta = trainer.fit(state, meta, data_fn, epochs=args.epochs,
                              on_epoch_end=on_epoch_end)
    unigram = float(np.log(args.vocab))
    rec = {"val_nll": losses[-1] if losses else None, "nll_curve": losses,
           "unigram_nll": round(unigram, 4), "world": world,
           "mesh": {k: int(v) for k, v in trainer.mesh.shape.items()}}
    print(f"[train_lm] {json.dumps(rec)}", flush=True)
    marker = os.environ.get("EDL_TPU_DEMO_MARKER")
    if marker:
        with open(marker, "a") as f:
            f.write("done " + json.dumps(rec) + "\n")


if __name__ == "__main__":
    main()
