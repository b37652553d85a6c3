"""Mamba-2 (state-space duality) token mixing: the chunked scan and the
one-token state update.

One layer's recurrence, per head h with a state ``S [P, N]`` (``P`` the
head size, ``N`` the state size)::

    a_t = exp(dt_t * A)                    A < 0 per head, dt_t > 0
    S_t = a_t * S_{t-1} + dt_t * x_t (outer) B_t
    y_t = S_t C_t

(``D * x`` and the gate are the caller's.)  Two ways to run it:

- :func:`ssd_scan` - a sequence of ``L`` tokens FROM an initial state,
  in chunks of ``chunk`` (the state-space-duality form: inside a chunk
  the quadratic ``(L o (C B^T)) X`` block, between chunks the
  recurrence over one state a chunk).  It returns the outputs, the
  state after each lane's last REAL token, and the state at one more
  position a lane (``snap_at``: where the serving engine snapshots a
  prompt for its prefix pool).  ``lengths`` masks positions past a
  lane's true length by zeroing their ``dt``: ``a = 1`` and no input,
  so the state stands still there.  With attention a padded position
  is harmless; here it would be a wrong state.  Plain einsums under
  the scope ``ssm/scan``; the chunks run one after another
  (``lax.scan``), so the quadratic block is ``[B, H, chunk, chunk]``
  float32 at a time whatever ``L`` is.
- :func:`ssm_step` - one token a slot against the slots' states, IN
  PLACE (a Pallas TPU kernel named ``ssm_step``; the states are
  aliased in and out).  A slot that ``live`` marks free is neither
  read nor written: its grid steps name the block the step before
  left in VMEM (``ops/decode_attention._fetch_plan``'s trick), so the
  pipeline fetches nothing and writes nothing back for it.
  :func:`ssm_step_reference` is the same update as einsums: every
  other backend's path and the kernel's parity reference, as
  ``ops/decode_attention.py`` keeps one.  It reads and writes every
  slot.

:func:`applies` is the dispatch rule, from what the caller can observe
and nothing else (a one-token call on a TPU without a mesh).  Off a TPU
the kernel runs in Pallas interpret mode (the tier-1 parity tests);
nothing selects it there.

Softplus, ``a``, the state and every sum over time are float32; the
matmul operands of the quadratic block are float32 too (the scan is
under 3% of a prefill's FLOPs at the published widths: its precision is
not worth trading).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.attention import _on_tpu
from edl_tpu.ops.decode_attention import _fetch_plan, blocks_fetched

# bytes of one state block of the step kernel: in and out, double
# buffered, is four of these in VMEM
_BLOCK_BYTES = 1 << 20


def applies(L: int, mesh) -> bool:
    """Whether a call takes the step kernel: a one-token step on a TPU
    with no mesh.  Everything else stays on the einsum paths."""
    return L == 1 and mesh is None and _on_tpu()


def _heads_of(v, H: int):
    """``[..., G, N] -> [..., H, N]``: head h reads group ``h // (H // G)``."""
    G = v.shape[-2]
    return v if G == H else jnp.repeat(v, H // G, axis=-2)


# -- the chunked scan ---------------------------------------------------------

def ssd_scan(x, dt, A, Bm, Cm, state, *, chunk: int, lengths=None,
             snap_at=None):
    """``x [B, L, H, P]``, ``dt [B, L, H]`` (after softplus), ``A [H]``
    (negative), ``Bm`` / ``Cm [B, L, G, N]``, ``state [B, H, P, N]``
    float32: the state BEFORE the first token.  Returns ``(y [B, L, H,
    P] float32, final [B, H, P, N], snap)``: the state after token
    ``lengths[b] - 1`` (``lengths`` None = ``L``) and, with ``snap_at``
    ``[B]`` int, the state after token ``snap_at[b] - 1`` (0 = the
    initial state; clipped to ``[0, lengths]``), else None."""
    with jax.named_scope("ssm/scan"):
        return _ssd_scan(x, dt, A, Bm, Cm, state, chunk, lengths, snap_at)


def _ssd_scan(x, dt, A, Bm, Cm, state, chunk, lengths, snap_at):
    B, L, H, P = x.shape
    f32 = jnp.float32
    Q = min(chunk, L)
    nc = -(-L // Q)
    pad = nc * Q - L
    t = jnp.arange(nc * Q)
    n_real = (jnp.full((B,), L, jnp.int32) if lengths is None
              else lengths.astype(jnp.int32))
    dt = jnp.pad(dt.astype(f32), ((0, 0), (0, pad), (0, 0)))
    dt = jnp.where((t[None, :] < n_real[:, None])[..., None], dt, 0.0)
    x = jnp.pad(x.astype(f32), ((0, 0), (0, pad), (0, 0), (0, 0)))
    Bm = jnp.pad(_heads_of(Bm.astype(f32), H), ((0, 0), (0, pad), (0, 0),
                                                (0, 0)))
    Cm = jnp.pad(_heads_of(Cm.astype(f32), H), ((0, 0), (0, pad), (0, 0),
                                                (0, 0)))
    want = snap_at is not None
    at = (jnp.clip(snap_at.astype(jnp.int32), 0, n_real) if want
          else jnp.zeros((B,), jnp.int32))

    def chunks(a):      # [B, nc * Q, ...] -> [nc, B, Q, ...]
        return jnp.moveaxis(a.reshape(B, nc, Q, *a.shape[2:]), 1, 0)

    tril = jnp.tril(jnp.ones((Q, Q), bool))
    s_idx = jnp.arange(Q)

    def one(carry, inp):
        S, snap = carry                       # [B, H, P, N] each
        xc, dtc, Bc, Cc, c = inp              # [B, Q, H, ...]
        cum = jnp.cumsum(dtc * A.astype(f32), axis=1)          # [B, Q, H]
        # inside the chunk: y_q = sum_{s <= q} (C_q . B_s)
        #   exp(cum_q - cum_s) dt_s x_s
        seg = cum[:, :, None, :] - cum[:, None, :, :]           # [B, q, s, H]
        decay = jnp.where(tril[None, :, :, None], jnp.exp(
            jnp.where(tril[None, :, :, None], seg, 0.0)), 0.0)
        cb = jnp.einsum("bqhn,bshn->bqsh", Cc, Bc)
        xdt = xc * dtc[..., None]                                # [B, Q, H, P]
        y = jnp.einsum("bqsh,bshp->bqhp", cb * decay, xdt)
        # the state the chunk started from, decayed to each position
        y = y + jnp.einsum("bqhn,bhpn,bqh->bqhp", Cc, S, jnp.exp(cum))
        # the chunk's own contribution to the state at its end
        to_end = jnp.exp(cum[:, -1:, :] - cum)                   # [B, Q, H]
        S_new = (S * jnp.exp(cum[:, -1, :])[..., None, None]
                 + jnp.einsum("bsh,bshp,bshn->bhpn", to_end, xdt, Bc))
        if want:
            # the state after token r - 1 of this chunk: one more
            # reduction of the chunk, masked to s < r
            r = at - c * Q                                       # [B]
            here = (r >= 0) & ((r < Q) | ((r == Q) & (c == nc - 1)))
            rc = jnp.clip(r, 0, Q)
            cum_r = jnp.where(
                (rc > 0)[:, None],
                jnp.take_along_axis(
                    cum, jnp.maximum(rc - 1, 0)[:, None, None], axis=1)[:, 0],
                0.0)                                             # [B, H]
            w = jnp.where((s_idx[None, :] < rc[:, None])[..., None],
                          jnp.exp(jnp.where(
                              (s_idx[None, :] < rc[:, None])[..., None],
                              cum_r[:, None, :] - cum, 0.0)), 0.0)
            S_r = (S * jnp.exp(cum_r)[..., None, None]
                   + jnp.einsum("bsh,bshp,bshn->bhpn", w, xdt, Bc))
            snap = jnp.where(here[:, None, None, None], S_r, snap)
        return (S_new, snap), y

    state = state.astype(f32)
    (final, snap), ys = jax.lax.scan(
        one, (state, state),
        (chunks(x), chunks(dt), chunks(Bm), chunks(Cm), jnp.arange(nc)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nc * Q, H, P)[:, :L]
    return y, final, (snap if want else None)


def ssm_recurrence(x, dt, A, Bm, Cm, state):
    """The plain recurrence, one token at a time (``lax.scan``): what
    :func:`ssd_scan` must equal.  Same arguments; returns ``(y, final)``
    and knows no lengths."""
    H = x.shape[2]
    f32 = jnp.float32
    Bh, Ch = _heads_of(Bm.astype(f32), H), _heads_of(Cm.astype(f32), H)

    def one(S, inp):
        xt, dtt, Bt, Ct = inp
        S = (S * jnp.exp(dtt * A.astype(f32))[..., None, None]
             + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :])
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ct)

    final, ys = jax.lax.scan(
        one, state.astype(f32),
        tuple(jnp.moveaxis(a, 1, 0) for a in
              (x.astype(f32), dt.astype(f32), Bh, Ch)))
    return jnp.moveaxis(ys, 0, 1), final


# -- the one-token step -------------------------------------------------------

def ssm_step_reference(state, x, dt, A, Bv, Cv, live=None):
    """One token a slot: ``state [B, H, P, N]`` float32, ``x [B, H, P]``,
    ``dt [B, H]`` (after softplus), ``A [H]``, ``Bv`` / ``Cv [B, G, N]``.
    Returns ``(y [B, H, P] float32, new state)``; a slot with
    ``live[b]`` false keeps its state and returns zeros.  Reads and
    writes every slot's state."""
    H = x.shape[1]
    f32 = jnp.float32
    Bh, Ch = _heads_of(Bv.astype(f32), H), _heads_of(Cv.astype(f32), H)
    dt = dt.astype(f32)
    new = (state * jnp.exp(dt * A.astype(f32))[..., None, None]
           + (dt[..., None] * x.astype(f32))[..., None] * Bh[:, :, None, :])
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch)
    if live is None:
        return y, new
    on = live[:, None, None, None]
    return jnp.where(live[:, None, None], y, 0.0), jnp.where(on, new, state)


def _step_kernel(on_ref, lead_ref, src_ref, lo_ref, hi_ref, s_ref, da_ref,
                 dtx_ref, b_ref, c_ref, y_ref, o_ref):
    """One (slot, head block): the states ``[hb, P, N]``; the step's rows
    head-minor, ``da [1, hb]`` and ``dt * x [P, hb]`` (a head's column
    broadcasts over the state's lanes as it lies), ``B`` / ``C [1, N]``;
    ``y [P, hb]``."""
    b = pl.program_id(0)
    hb = s_ref.shape[0]

    @pl.when(on_ref[b] != 0)
    def _():
        da, dtx = da_ref[...], dtx_ref[...]
        bv, cv = b_ref[...], c_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, y_ref.shape, 1)
        y = jnp.zeros(y_ref.shape, jnp.float32)
        for i in range(hb):
            new = s_ref[i] * da[:, i:i + 1] + dtx[:, i:i + 1] * bv
            o_ref[i] = new
            y = jnp.where(lane == i,
                          jnp.sum(new * cv, axis=-1, keepdims=True), y)
        y_ref[...] = y

    @pl.when(on_ref[b] == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    # free slots ahead of the first live one name ITS first block, which
    # no step has written: hand it through, or the write-back at the
    # first change of block (at the grid's end, when no slot is live)
    # would store what the output buffer happened to hold
    @pl.when(lead_ref[b] != 0)
    def _():
        o_ref[...] = s_ref[...]


def step_block(H: int, P: int, N: int, G: int = 1) -> int:
    """Heads a grid step of the kernel holds: the largest divisor of one
    group's heads whose state block stays within ``_BLOCK_BYTES``."""
    per = H // G
    hb = 1
    for cand in range(1, per + 1):
        if per % cand == 0 and cand * P * N * 4 <= _BLOCK_BYTES:
            hb = cand
    return hb


def _step_plan(live, nb: int):
    """What the step kernel's index maps read, ``(on, lead, src, lo,
    hi)`` a slot: the attend kernel's fetch plan over ``nb`` head
    blocks.  A live slot names its own blocks; a free slot the block
    the step before it left in VMEM (its last live predecessor's last
    block, or the first live slot's first block when it has none:
    ``lead``)."""
    src, lo, hi = _fetch_plan(jnp.where(live, nb, 0), 1)
    on = live.astype(jnp.int32)
    lead = (jnp.cumsum(on) == 0).astype(jnp.int32)
    return on, lead, src, lo, hi


def slots_fetched(live, H: int, P: int, N: int, G: int = 1):
    """Slot states one :func:`ssm_step` call fetches and writes back,
    float32 (whole slots' worth of state blocks), COUNTED from the plan
    the kernel runs under: the pipeline fetches a block when a grid
    step names another than the step before it, so the count is the
    changes of block along the grid, its first step included.  The live
    slots when free slots cost nothing; one block when no slot is
    live."""
    nb = H // step_block(H, P, N, G)
    _, _, src, lo, hi = _step_plan(live, nb)
    return blocks_fetched(src, lo, hi, nb) / nb


def ssm_step(state, x, dt, A, Bv, Cv, live, *, interpret=None):
    """:func:`ssm_step_reference` as one Pallas call, the states updated
    in place (donate or carry ``state``: it is aliased in and out).  A
    free slot's state is neither fetched nor written back."""
    B, H, P, N = state.shape
    G = Bv.shape[1]
    f32 = jnp.float32
    hb = step_block(H, P, N, G)
    nb = H // hb
    per_group = (H // G) // hb            # head blocks a group
    dt = dt.astype(f32)

    def head_minor(a):      # [B, H, ...] -> [B, nb, ..., hb]
        return jnp.moveaxis(a.reshape(B, nb, hb, *a.shape[2:]), 2, -1)

    da = head_minor(jnp.exp(dt * A.astype(f32))[:, :, None])     # [B,nb,1,hb]
    dtx = head_minor(dt[..., None] * x.astype(f32))              # [B,nb,P,hb]
    Bv = Bv.astype(f32)[:, :, None, :]                           # [B,G,1,N]
    Cv = Cv.astype(f32)[:, :, None, :]
    on, lead, src, lo, hi = _step_plan(live, nb)

    def held(b, j, on, lead, src, lo, hi):
        return src[b], jnp.clip(j, lo[b], hi[b])

    def s_index(*step):
        s, j = held(*step)
        return s, j, 0, 0

    def g_index(*step):
        s, j = held(*step)
        return s, j // per_group, 0, 0

    s_spec = pl.BlockSpec((None, hb, P, N), s_index)
    y, new = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B, nb),
            in_specs=[s_spec,
                      pl.BlockSpec((None, None, 1, hb), s_index),
                      pl.BlockSpec((None, None, P, hb), s_index),
                      pl.BlockSpec((None, None, 1, N), g_index),
                      pl.BlockSpec((None, None, 1, N), g_index)],
            out_specs=[pl.BlockSpec((None, None, P, hb),
                                    lambda b, j, *_: (b, j, 0, 0)),
                       s_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the five prefetched scalars
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=(not _on_tpu()) if interpret is None else interpret,
        name="ssm_step",
    )(on, lead, src, lo, hi, state, da, dtx, Bv, Cv)
    # [B, nb, P, hb] -> [B, H, P]
    return jnp.moveaxis(y, -1, 2).reshape(B, H, P), new
