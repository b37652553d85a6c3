"""The training cells: ``ElasticTrainer``'s own loop, machinery on.

One process holds the chips and is the one under test.  It gives the
trainer what ``collective/launch.py`` gives a trainer it spawns: a
coordination store (a real server in a child that never imports JAX, a
real client over the wire), a pod id, a cluster stage and a checkpoint
directory, so that the step ledger, the memstate tee, the delta
replicator, the heartbeat and the preempt check are all built and run
at their defaults.

The window is driven from the input side: ``data_fn`` is the
benchmark's generator, and the trainer pulls batches from it on its
own thread, so the generator knows exactly which steps have been
dispatched.  At the window's edges it waits for the newest step's loss
(``block_until_ready``), so both edges are instants at which the device
had finished everything dispatched.  The trainer saves at the end of an
epoch; the epoch here outlasts the run and is abandoned at the
window's end, so no save falls inside (or after) the window.
"""

from __future__ import annotations

import collections
import dataclasses
import importlib
import logging
import os
import time

import numpy as np

from . import common

WARM_STEPS = 3          # the first compiles; the ledger starts at the 2nd
TRACE_AFTER = 2         # traced run: window steps before the trace starts
TRACE_STEPS = 4
# The trainer dispatches steps without waiting for them, and nothing in
# it bounds how far the host runs ahead (measured: 30 steps dispatched
# in 10 s that the chip needed 16 s for).  The generator keeps the host
# at most this many steps ahead of the device, so that the window lasts
# --seconds and not twice that; the device never waits for it.
RUN_AHEAD = 2
# System loss (bf16 matmuls, f32 accumulation and softmax, splash
# attention, fused CE) against the float32 reference on the same rows at
# initialisation, where the loss is about ln(vocab) = 10.4.  bf16 has 8
# bits of mantissa; rounding errors of the logits are independent over
# 4096 x rows positions and mostly cancel in the mean.  Measured on the
# chip (PERF.md, Findings): |difference| of a few 1e-4.  An fp8 or int8
# path, or bf16 accumulation, moves the mean loss by 1e-2 or more.
LOSS_TOLERANCE = 3e-3


class _WindowDone(BaseException):
    """Leaves ``fit`` from inside ``data_fn`` without ending the epoch
    (which would save 8 GB).  Not an ``Exception``: ``fit`` turns those
    into a live reshard when the delta path is armed."""


class _StepTap:
    """The jitted step the trainer built, with a count of calls, a handle
    on the newest loss and a host span around the dispatch."""

    def __init__(self, fn):
        self._fn = fn
        self.calls = 0
        self.loss = None
        self.first_loss = None
        self.recent = collections.deque(maxlen=RUN_AHEAD)

    def __call__(self, state, batch, rng):
        import jax
        with jax.profiler.TraceAnnotation("bench/train_step_dispatch"):
            state, metrics = self._fn(state, batch, rng)
        self.calls += 1
        self.loss = metrics["loss"]
        self.recent.append(self.loss)
        if self.first_loss is None:
            self.first_loss = metrics["loss"]
        return state, metrics

    def __getattr__(self, name):
        return getattr(self._fn, name)


class _Complaints(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines: list[str] = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def _counters() -> dict:
    from edl_tpu.obs import ledger as obs_ledger
    from edl_tpu.obs import metrics as obs_metrics
    out = {f"phase_{p}_s": obs_ledger.PHASE_SECONDS.labels(phase=p).sum
           for p in obs_ledger.PHASES}
    step = obs_metrics.REGISTRY.get("edl_train_step_seconds")
    out["step_s"] = step.sum if step is not None else 0.0
    out["steps"] = step.count if step is not None else 0
    delta = obs_metrics.REGISTRY.get("edl_delta_bytes_total")
    out["delta_bytes"] = delta.value if delta is not None else 0.0
    return out


def run(cell: dict, conf: dict, traffic: dict, args, t_start: float) -> dict:
    children = common.Children()
    try:
        return _run(cell, conf, traffic, args, t_start, children)
    finally:
        children.stop()


def _run(cell, conf, traffic, args, t_start, children) -> dict:
    import model
    import reference
    rc = conf["run"]
    chips = cell["chips"]
    phases = common.Phases(t_start)
    ep, store = children.coord()
    phases.mark("imports+coord")
    ckpt_dir = os.path.join(children.tmp, "ckpt")
    env = {"EDL_TPU_JOB_ID": f"bench-{cell['name']}",
           "EDL_TPU_COORD_ENDPOINTS": ep, "EDL_TPU_POD_ID": "benchpod0",
           "EDL_TPU_CLUSTER_STAGE": "benchstage0",
           "EDL_TPU_CKPT_DIR": ckpt_dir, "EDL_TPU_TRAINERS_NUM": "1"}
    os.environ.update(env)

    import jax
    import jax.numpy as jnp
    import optax

    from edl_tpu.cluster.env import TrainerEnv
    from edl_tpu.models import transformer as tf_mod
    from edl_tpu.models.logical import logical_axes_from_paths
    from edl_tpu.models.transformer import (TransformerLM, auto_layout,
                                            lm_loss, lm_loss_fused)
    from edl_tpu.parallel import MeshSpec
    from edl_tpu.train import ElasticTrainer, TrainConfig

    complaints = _Complaints()
    logging.getLogger("edl_tpu").addHandler(complaints)
    tenv = TrainerEnv()
    devices = jax.devices()[:chips]
    seq, per_chip = rc["seq_len"], rc["per_chip_batch"]
    spec = MeshSpec(dp=rc["mesh"]["dp"], fsdp=rc["mesh"]["fsdp"],
                    tp=rc["mesh"]["tp"])
    ways = rc["mesh"]["dp"] * rc["mesh"]["fsdp"]
    batch = per_chip * ways
    cfg = model.transformer_config(conf, max_len=seq)
    # the layout the shipped trainer script picks (train_lm.py): remat
    # only when the batch does not fit, unrolled layers when shallow
    auto = auto_layout(cfg, per_chip, seq)
    cfg = dataclasses.replace(cfg, remat=auto.remat,
                              scan_layers=auto.scan_layers)
    lm = TransformerLM(cfg)

    def loss_fn(params, extra, b, rng):
        if rc["fused_ce"]:
            h, _ = lm.apply({"params": params}, b["ids"][:, :-1],
                            return_hidden=True, with_aux=True)
            loss = lm_loss_fused(params, h, b["ids"][:, 1:], cfg,
                                 block_size=rc["ce_block"])
        else:
            logits = lm.apply({"params": params}, b["ids"][:, :-1])
            loss = lm_loss(logits, b["ids"][:, 1:])
        return loss, (extra, {})

    trainer = ElasticTrainer(
        loss_fn, TrainConfig(mesh_spec=spec, checkpoint_dir=ckpt_dir,
                             global_batch_size=batch, log_every=0),
        store=store, tenv=tenv, devices=devices)
    if trainer.mesh.size > 1:
        # splash runs under shard_map on each device's own rows
        cfg = dataclasses.replace(cfg, mesh=trainer.mesh)
        lm = TransformerLM(cfg)
    from edl_tpu.parallel.mesh import batch_divisor
    b0 = batch_divisor(trainer.mesh)

    def make_params(key):
        return lm.init(key, jnp.zeros((b0, 8), jnp.int32))["params"]

    def init():
        return make_params(jax.random.key(0)), None

    shape = jax.eval_shape(lambda: init()[0])
    logical = logical_axes_from_paths(shape, tf_mod.LOGICAL_RULES)
    state, meta = trainer.restore_or_create(
        init, optax.adamw(rc["learning_rate"]), param_logical=logical)
    # The trainer's init takes no argument, so a seed in it would be a
    # constant of the program and every new seed a new compile (10 s of
    # set-up, measured).  The state is born from a fixed key through the
    # trainer's own sharded init; the parameters are then drawn again
    # from --seed by one program that takes the key as its argument and
    # lays them out as the trainer did.
    state = state.replace(params=jax.jit(
        make_params, out_shardings=jax.tree.map(
            lambda a: a.sharding, state.params))(
                jax.random.key(args.seed % (1 << 31))))
    phases.mark("jax+trainer+state")
    engaged = {
        "tee": getattr(trainer.ckpt, "_tee", None) is not None,
        "delta_replicator": getattr(trainer, "_delta_rep", None) is not None,
        "ledger": bool(trainer._ledger.enabled),
        "preempt_check": bool(tenv.pod_id and tenv.cluster_stage),
    }

    gen_mod = importlib.import_module(f"generators.{traffic['generator']}")
    source = gen_mod.batches(traffic, args.seed, batch, seq,
                             conf["vocab_size"])

    # -- correctness, outside the window: first-step loss at these widths
    sample = next(gen_mod.batches(traffic, args.seed + 1, b0, seq,
                                  conf["vocab_size"]))["ids"]
    from edl_tpu.parallel.sharding import shard_host_batch
    gsample = shard_host_batch({"ids": sample}, trainer.mesh, trainer.rules)
    sys_loss = float(jax.jit(lambda p, b: loss_fn(p, None, b, None)[0])(
        state.params, gsample))
    ref_loss = reference.loss(conf, state.params, sample)
    print(f"[bench] first-step loss: system {sys_loss:.6f} reference "
          f"{ref_loss:.6f} diff {abs(sys_loss - ref_loss):.2e} "
          f"(tolerance {LOSS_TOLERANCE})", flush=True)

    phases.mark("reference check")
    tap = _StepTap(trainer.step_fn)
    trainer._step_fn = tap   # the only handle on the loop's device work
    trace = (common.TraceWindow(os.path.join(children.tmp, "trace"))
             if args.trace else None)
    mark: dict = {}

    def sync():
        if tap.loss is not None:
            jax.block_until_ready(tap.loss)

    def pace():
        # wait for the step RUN_AHEAD back; what the wait costs is kept
        # apart, because the trainer's ledger books it as data_wait
        if len(tap.recent) == RUN_AHEAD:
            t = time.monotonic()
            jax.block_until_ready(tap.recent[0])
            mark["paced_s"] = mark.get("paced_s", 0.0) + time.monotonic() - t

    def data_fn(epoch):
        # the trainer asks for batch j+1 after it has dispatched steps
        # 0..j-1 (it stages one batch ahead), so at each ``yield`` below
        # ``tap.calls`` is the number of steps dispatched so far
        for _ in range(WARM_STEPS + 1):
            yield next(source)
        sync()
        mark["setup_s"] = time.monotonic() - t_start
        phases.mark(f"{WARM_STEPS + 1} warm steps")
        print(phases.line(), flush=True)
        mark["c0"], mark["n0"] = _counters(), tap.calls
        mark["loss0"] = float(tap.loss)
        mark["paced_s"] = 0.0
        mark["t0"] = t0 = time.monotonic()
        while time.monotonic() - t0 < args.seconds:
            pace()
            done = tap.calls - mark["n0"]
            if trace and done == TRACE_AFTER and trace.t_start is None:
                sync()
                mark["trace_n0"] = tap.calls
                trace.start()
            if trace and trace.t_start and trace.t_stop is None and \
                    tap.calls - mark["trace_n0"] >= TRACE_STEPS:
                sync()
                mark["trace_steps"] = tap.calls - mark["trace_n0"]
                trace.stop()
            yield next(source)
        sync()
        mark["t1"] = time.monotonic()
        mark["c1"], mark["n1"] = _counters(), tap.calls
        mark["loss1"] = float(tap.loss)
        raise _WindowDone

    try:
        trainer.fit(state, meta, data_fn, epochs=1,
                    rng=jax.random.key(args.seed % (1 << 31)))
        raise RuntimeError("the epoch ended before the window did")
    except _WindowDone:
        pass
    if trace and trace.t_stop is None:
        raise RuntimeError("the window ended before the traced steps did: "
                           "raise --seconds")

    steps = mark["n1"] - mark["n0"]
    window = mark["t1"] - mark["t0"]
    tokens = steps * batch * seq
    first = float(tap.first_loss)
    losses = {"first_step": first, "window_start": mark["loss0"],
              "window_end": mark["loss1"]}
    checks = {
        "reference_loss": abs(sys_loss - ref_loss) <= LOSS_TOLERANCE,
        "loss_finite": bool(np.isfinite(list(losses.values())).all()),
        "loss_falls": mark["loss1"] < mark["loss0"],
        "machinery_engaged": all(engaged.values()),
        "no_complaints": not [c for c in complaints.lines
                              if "unavailable" in c or "failed" in c],
    }
    print(f"[bench] steps {steps} in {window:.3f}s, batch {batch} x {seq}, "
          f"losses {losses}, engaged {engaged}, checks {checks}, "
          f"complaints {complaints.lines[:5]}", flush=True)
    facts = common.device_facts()
    counters = {k: mark["c1"][k] - mark["c0"][k] for k in mark["c0"]}
    counters.update(paced_s=mark["paced_s"], window_steps=steps, window_s=window, batch=batch,
                    seq=seq, chips=chips,
                    traced_steps=mark.get("trace_steps", 0))
    return {
        "correct": all(checks.values()), "attempted": steps, "failed": 0,
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens / window / chips,
            "setup_s": mark["setup_s"]},
        "device": facts, "counters": counters, "records": [],
        "trace": trace.reduce() if trace else None,
        "trace_span_s": (trace.t_stop - trace.t_start) if trace else None,
    }
