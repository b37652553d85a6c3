"""Mamba-1 (selective scan) token mixing: the scan and the one-token
state update.

One layer's recurrence, per inner channel d with a state ``S [N]``
(``N`` the state size, 16 as published)::

    S_t[n, d] = exp(dt_t[d] * A[n, d]) * S_{t-1}[n, d] + dt_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n S_t[n, d] C_t[n]

(``D * x`` and the gate are the caller's.)  ``A`` is a matrix, a decay a
(channel, state) pair: there is no head to share one scalar decay, so
Mamba-2's chunked matmul form (``ops/ssm.ssd_scan``) does not apply.
The state is kept ``[B, N, inner]``: the wide axis on the lanes (N = 16
on the lanes would be padded to 128 in HBM and read eight times over).

- :func:`selective_scan` - ``L`` tokens FROM an initial state, one
  token after another (``lax.scan``: every step is an elementwise
  update of ``[B, N, inner]``), under the scope ``mamba1/scan``.  It
  returns the outputs, the state after each lane's last REAL token
  (``lengths`` zeroes ``dt`` past it: ``a = 1`` and no input, the state
  stands still, as ``ssd_scan`` masks), and with ``snap_at`` the state
  at one more position a lane.
- :func:`mamba1_step` - one token a slot against the slots' states, IN
  PLACE (a Pallas TPU kernel named ``mamba1_step``; the states aliased
  in and out).  A slot that ``live`` marks free is neither read nor
  written (``ops/ssm.ssm_step``'s fetch plan, one block a slot).
  :func:`mamba1_step_reference` is the same update as plain
  ``jax.numpy``: every other backend's path and the kernel's parity
  reference.  It reads and writes every slot.

:func:`applies` is ``ops/ssm.applies``: a one-token call on a TPU
without a mesh.  Softplus, the decay, the state and every sum are
float32.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops.attention import _on_tpu
from edl_tpu.ops.decode_attention import blocks_fetched
from edl_tpu.ops.ssm import _step_plan, applies  # noqa: F401  (the rule)

# time steps of the scan unrolled into one loop body
_UNROLL = 8


def selective_scan(x, dt, A, Bm, Cm, state, *, lengths=None, snap_at=None):
    """``x`` / ``dt [B, L, inner]`` (``dt`` after softplus), ``A [N,
    inner]`` (negative), ``Bm`` / ``Cm [B, L, N]``, ``state [B, N,
    inner]`` float32: the state BEFORE the first token.  Returns ``(y
    [B, L, inner] float32, final [B, N, inner], snap)``: the state after
    token ``lengths[b] - 1`` (``lengths`` None = ``L``) and, with
    ``snap_at [B]`` int, the state after token ``snap_at[b] - 1`` (0 =
    the initial state; clipped to ``[0, lengths]``), else None."""
    with jax.named_scope("mamba1/scan"):
        return _selective_scan(x, dt, A, Bm, Cm, state, lengths, snap_at)


def _selective_scan(x, dt, A, Bm, Cm, state, lengths, snap_at):
    B, L, _ = x.shape
    f32 = jnp.float32
    n_real = (jnp.full((B,), L, jnp.int32) if lengths is None
              else lengths.astype(jnp.int32))
    dt = jnp.where((jnp.arange(L)[None, :] < n_real[:, None])[..., None],
                   dt.astype(f32), 0.0)
    dtx = dt * x.astype(f32)
    want = snap_at is not None
    at = (jnp.clip(snap_at.astype(jnp.int32), 0, n_real) if want
          else jnp.zeros((B,), jnp.int32))
    A = A.astype(f32)

    def one(carry, inp):
        S, snap = carry                                   # [B, N, inner]
        dtt, dtxt, bt, ct, t = inp
        S = (S * jnp.exp(dtt[:, None, :] * A[None])
             + bt[:, :, None] * dtxt[:, None, :])
        if want:
            snap = jnp.where((at == t + 1)[:, None, None], S, snap)
        return (S, snap), jnp.sum(S * ct[:, :, None], axis=1)

    state = state.astype(f32)
    (final, snap), ys = jax.lax.scan(
        one, (state, state),
        (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(dtx, 1, 0),
         jnp.moveaxis(Bm.astype(f32), 1, 0),
         jnp.moveaxis(Cm.astype(f32), 1, 0), jnp.arange(L)),
        unroll=min(_UNROLL, L))
    return jnp.moveaxis(ys, 0, 1), final, (snap if want else None)


# -- the one-token step -------------------------------------------------------

def mamba1_step_reference(state, x, dt, A, Bv, Cv, live=None):
    """One token a slot: ``state [B, N, inner]`` float32, ``x`` / ``dt
    [B, inner]`` (``dt`` after softplus), ``A [N, inner]``, ``Bv`` /
    ``Cv [B, N]``.  Returns ``(y [B, inner] float32, new state)``; a
    slot with ``live[b]`` false keeps its state and returns zeros.
    Reads and writes every slot's state."""
    f32 = jnp.float32
    dt = dt.astype(f32)
    new = (state * jnp.exp(dt[:, None, :] * A.astype(f32)[None])
           + Bv.astype(f32)[:, :, None] * (dt * x.astype(f32))[:, None, :])
    y = jnp.sum(new * Cv.astype(f32)[:, :, None], axis=1)
    if live is None:
        return y, new
    return (jnp.where(live[:, None], y, 0.0),
            jnp.where(live[:, None, None], new, state))


def _step_kernel(on_ref, lead_ref, src_ref, lo_ref, hi_ref, s_ref, a_ref,
                 dt_ref, dtx_ref, b_ref, c_ref, y_ref, o_ref):
    """One slot: the state ``[N, inner]``, ``A [N, inner]``, ``dt`` and
    ``dt * x [1, inner]`` (a row broadcasts over the state's sublanes),
    ``B`` / ``C [N, 1]`` (a column over its lanes); ``y [1, inner]``."""
    b = pl.program_id(0)

    @pl.when(on_ref[b] != 0)
    def _():
        new = (s_ref[...] * jnp.exp(dt_ref[...] * a_ref[...])
               + b_ref[...] * dtx_ref[...])
        o_ref[...] = new
        y_ref[...] = jnp.sum(new * c_ref[...], axis=0, keepdims=True)

    @pl.when(on_ref[b] == 0)
    def _():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    # free slots ahead of the first live one name ITS block, which no
    # step has written: hand it through (``ops/ssm._step_kernel``)
    @pl.when(lead_ref[b] != 0)
    def _():
        o_ref[...] = s_ref[...]


def slots_fetched(live):
    """Slot states one :func:`mamba1_step` call fetches and writes back,
    float32, COUNTED from the plan the kernel runs under (one block a
    slot): the live slots when free slots cost nothing; one block when
    no slot is live."""
    _, _, src, lo, hi = _step_plan(live, 1)
    return blocks_fetched(src, lo, hi, 1)


def mamba1_step(state, x, dt, A, Bv, Cv, live, *, interpret=None):
    """:func:`mamba1_step_reference` as one Pallas call, the states
    updated in place (donate or carry ``state``: it is aliased in and
    out).  A free slot's state is neither fetched nor written back."""
    B, N, Di = state.shape
    f32 = jnp.float32
    dt = dt.astype(f32)[:, None, :]                              # [B,1,Di]
    dtx = dt * x.astype(f32)[:, None, :]
    Bv = Bv.astype(f32)[:, :, None]                              # [B,N,1]
    Cv = Cv.astype(f32)[:, :, None]
    on, lead, src, lo, hi = _step_plan(live, 1)

    def held(b, on, lead, src, lo, hi):
        return src[b], 0, 0

    def own(b, *_):
        return b, 0, 0

    s_spec = pl.BlockSpec((None, N, Di), held)
    row = pl.BlockSpec((None, 1, Di), own)
    col = pl.BlockSpec((None, N, 1), own)
    y, new = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B,),
            in_specs=[s_spec,
                      pl.BlockSpec((N, Di), lambda b, *_: (0, 0)),
                      row, row, col, col],
            out_specs=[row, s_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, 1, Di), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the five prefetched scalars
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=(not _on_tpu()) if interpret is None else interpret,
        name="mamba1_step",
    )(on, lead, src, lo, hi, state, A.astype(f32), dt, dtx, Bv, Cv)
    return y[:, 0], new
