"""Kimi delta attention (a gated delta rule with a per-channel decay): the
chunked form and the one-token state update.

One layer's recurrence, per head with a state ``S [K, V]`` (``K`` the key
width, ``V`` the value width)::

    S'  = Diag(exp(g_t)) S_{t-1}           g_t <= 0, one log decay a key channel
    u_t = v_t - S'^T k_t                   the delta correction
    S_t = S' + beta_t k_t u_t^T            0 <= beta_t <= 2
    o_t = S_t^T q_t

``beta_t`` is the caller's: ``sigmoid(b)`` in [0, 1], or ``2 sigmoid(b)``
in [0, 2] under ``TransformerConfig.kda_neg_eigval`` (a published
``kda_allow_neg_eigval``; the benchmark's ``solar-open2-250b-serve-ep8``
doubles it, ``kimi-linear-48b-a3b-serve-ep4`` does not).  With ``k_t`` of
unit norm the transition ``I - beta_t k_t k_t^T`` then has an eigenvalue
``1 - beta_t`` in [-1, 1] and not in [0, 1]: a state can flip along a
key, not only fade.  Nothing below assumes the smaller range; the chunked
form's unit triangular solve is exact either way and worse conditioned
(its off-diagonal entries double), which is what its parity limits are
read for (``tests/test_solar_open2.py`` with betas in (1, 2]; on the
chip PERF.md section 6, PR 52).

(the convolutions, the L2 norms, the scale on ``q``, the gate and the
output norm are the caller's: ``transformer.KDAMixer``).  Three ways to
run it, shaped like ``ops/ssm.py``:

- :func:`kda_chunked` - ``L`` tokens FROM an initial state, in chunks of
  ``chunk``.  Inside a chunk, with ``G`` the running sum of ``g`` over
  the chunk's positions, the corrections solve one unit lower triangular
  system (the WY / UT transform)::

      (I + tril(A, -1) Diag(beta)) U = V - (K * exp(G)) S_0
      A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])

  and the outputs and the state at the chunk's end are sums over ``U``.
  Every decay is the exponential of a DIFFERENCE ``G_t - G_s`` with
  ``s <= t`` (never ``exp(G_t) / exp(G_s)``: a channel that has decayed
  to nothing would divide by zero).  Between chunks the recurrence runs
  over one state a chunk (``lax.scan``), so the blocks are ``[B, H,
  chunk, chunk(, K)]`` float32 whatever ``L`` is, and ``[H, chunk,
  chunk, K]`` whatever ``B`` is where one lane's reaches ``_LANE_BLOCK``
  (:func:`lanes_mapped`: the lanes then run one after another).  It
  returns the
  outputs, the state after each lane's last REAL token, and the state at
  one more position a lane (``snap_at``: where the serving engine
  snapshots a prompt for its prefix pool).  ``lengths`` masks positions
  past a lane's true length by zeroing their ``g`` and ``beta``: no
  decay and no update, so the state stands still there.  Plain einsums
  under the scope ``kda/chunk``.
- :func:`kda_step` - one token a slot against the slots' states, IN
  PLACE (a Pallas TPU kernel named ``kda_step``; the states are aliased
  in and out).  A slot that ``live`` marks free is neither read nor
  written (``ops/ssm.py``'s plan over ``ops/decode_attention.
  _fetch_plan``).  :func:`kda_step_reference` is the same update as
  einsums: every other backend's path and the kernel's parity reference.
- :func:`kda_recurrence` - the plain recurrence, one token at a time:
  what the other two must equal.

:func:`applies` is the dispatch rule, from what the caller can observe
and nothing else (a one-token call on a TPU without a mesh).  Off a TPU
the kernel runs in Pallas interpret mode (the tier-1 parity tests);
nothing selects it there.

Everything is float32, and the chunk's matmuls ask for the highest
precision: the triangular solve amplifies what a bfloat16 pass rounds
away, and the blocks are small (under 2% of a prefill's FLOPs at the
published widths).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from edl_tpu.ops import ssm
from edl_tpu.ops.attention import _on_tpu

_HI = jax.lax.Precision.HIGHEST


def applies(L: int, mesh) -> bool:
    """Whether a call takes the step kernel: a one-token step on a TPU
    with no mesh (``ops/ssm.applies``: one rule for both recurrences)."""
    return ssm.applies(L, mesh)


def step_block(H: int, K: int, V: int) -> int:
    """Heads a grid step of the kernel holds (``ops/ssm.step_block``)."""
    return ssm.step_block(H, K, V)


def slots_fetched(live, H: int, K: int, V: int):
    """Slot states one :func:`kda_step` call fetches and writes back,
    counted from the plan the kernel runs under: ``ops/ssm.
    slots_fetched``, whose plan this kernel shares."""
    return ssm.slots_fetched(live, H, K, V)


# -- the chunked form ---------------------------------------------------------

# One lane's decay block ``[H, chunk, chunk, K]`` float32 from which a
# call's lanes run one after another: 128 MiB, 64 heads of 128 at a
# chunk of 64 (32 heads are 64 MiB and keep their lanes side by side).
_LANE_BLOCK = 128 << 20


def lanes_mapped(H: int, K: int, chunk: int) -> bool:
    """Whether a multi-lane :func:`kda_chunked` call runs its lanes one
    after another (``lax.map``) and not side by side: where ONE lane's
    ``[H, chunk, chunk, K]`` float32 decay block reaches ``_LANE_BLOCK``.
    From the widths alone, whatever the call's length, so a layer's
    multi-lane programs are all of one form.  Two grounds.  A scan step
    of one such lane already holds several blocks of 128 MiB and fills
    the chip: two lanes x 512 tokens mapped take twice one lane's 18 ms
    on a v5e (PR 52), so side by side buys nothing.  And side by side the
    chip's compiler has built a program that NEVER RETURNS: two lanes x
    8 chunks x 64 heads through two such layers in a row (one layer, one
    lane of 16 chunks, two lanes of 4 chunks, 32 heads all return;
    ``scripts/chip_kda_two_lane_prefill.py`` runs both forms).  The
    fault is below this file; a lane at a time, each inner program is
    the one-lane program that runs.  (Every caller's lanes: a training
    batch at these widths is mapped too, and one SHARDED over a mesh
    would serialise its shards; no configuration here trains this
    stack or serves it on a mesh, which the engine refuses by name.)"""
    return 4 * H * chunk * chunk * K >= _LANE_BLOCK


def kda_chunked(q, k, v, g, beta, state, *, chunk: int = 64, lengths=None,
                snap_at=None):
    """``q`` / ``k [B, L, H, K]`` (normalised, ``q`` scaled), ``v [B, L,
    H, V]``, ``g [B, L, H, K]`` (log decays, <= 0), ``beta [B, L, H]``,
    ``state [B, H, K, V]`` float32: the state BEFORE the first token.
    Returns ``(o [B, L, H, V] float32, final [B, H, K, V], snap)``: the
    state after token ``lengths[b] - 1`` (``lengths`` None = ``L``) and,
    with ``snap_at`` ``[B]`` int, the state after token ``snap_at[b] -
    1`` (0 = the initial state; clipped to ``[0, lengths]``), else
    None."""
    B, L, H, K = q.shape
    with jax.named_scope("kda/chunk"):
        if B == 1 or not lanes_mapped(H, K, chunk):
            return _kda_chunked(q, k, v, g, beta, state, chunk, lengths,
                                snap_at)
        n = (jnp.full((B,), L, jnp.int32) if lengths is None else lengths)
        at = jnp.zeros((B,), jnp.int32) if snap_at is None else snap_at

        def lane(args):
            o, final, snap = _kda_chunked(*(a[None] for a in args[:6]),
                                          chunk, args[6][None],
                                          args[7][None])
            return o[0], final[0], snap[0]

        o, final, snap = jax.lax.map(lane, (q, k, v, g, beta, state, n, at))
        return o, final, (None if snap_at is None else snap)


def _kda_chunked(q, k, v, g, beta, state, chunk, lengths, snap_at):
    B, L, H, K = q.shape
    f32 = jnp.float32
    Q = min(chunk, L)
    nc = -(-L // Q)
    pad = nc * Q - L
    t = jnp.arange(nc * Q)
    n_real = (jnp.full((B,), L, jnp.int32) if lengths is None
              else lengths.astype(jnp.int32))
    real = t[None, :] < n_real[:, None]                          # [B, nc*Q]

    def padded(a):
        return jnp.pad(a.astype(f32),
                       ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    q, k, v = padded(q), padded(k), padded(v)
    g = jnp.where(real[..., None, None], padded(g), 0.0)
    beta = jnp.where(real[..., None], padded(beta), 0.0)
    want = snap_at is not None
    at = (jnp.clip(snap_at.astype(jnp.int32), 0, n_real) if want
          else jnp.zeros((B,), jnp.int32))

    def chunks(a):      # [B, nc * Q, H, ...] -> [nc, B, H, Q, ...]
        a = a.reshape(B, nc, Q, *a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 3, 2), 1, 0)

    s_idx = jnp.arange(Q)
    lower = s_idx[:, None] > s_idx[None, :]                      # s < t
    upto = s_idx[:, None] >= s_idx[None, :]                      # s <= t

    def one(carry, inp):
        S, snap = carry                       # [B, H, K, V] each
        qc, kc, vc, gc, bc, c = inp           # [B, H, Q, ...]
        G = jnp.cumsum(gc, axis=2)                               # [B, H, Q, K]
        # exp(G_t - G_s) for s <= t, a key channel: [B, H, t, s, K]
        seg = G[:, :, :, None, :] - G[:, :, None, :, :]
        decay = jnp.exp(jnp.where(upto[:, :, None], seg, -jnp.inf))
        kk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", kc, kc, decay)
        qk = jnp.einsum("bhtc,bhsc,bhtsc->bhts", qc, kc, decay)
        eG = jnp.exp(G)
        rhs = vc - jnp.einsum("bhtc,bhcv->bhtv", kc * eG, S, precision=_HI)
        lhs = (jnp.where(lower, kk, 0.0) * bc[:, :, None, :]
               + jnp.eye(Q, dtype=f32))
        U = jax.scipy.linalg.solve_triangular(lhs, rhs, lower=True,
                                              unit_diagonal=True)
        bU = U * bc[..., None]                                   # beta_s u_s
        o = (jnp.einsum("bhtc,bhcv->bhtv", qc * eG, S, precision=_HI)
             + jnp.einsum("bhts,bhsv->bhtv", qk, bU, precision=_HI))
        # the chunk's own contribution to the state at its end
        to_end = jnp.exp(G[:, :, -1:, :] - G)                    # [B, H, Q, K]
        S_new = (S * eG[:, :, -1, :, None]
                 + jnp.einsum("bhsc,bhsv->bhcv", kc * to_end, bU,
                              precision=_HI))
        if want:
            # the state after token r - 1 of this chunk: one more
            # reduction of the chunk, masked to s < r
            r = at - c * Q                                       # [B]
            here = (r >= 0) & ((r < Q) | ((r == Q) & (c == nc - 1)))
            rc = jnp.clip(r, 0, Q)
            G_r = jnp.where(
                (rc > 0)[:, None, None],
                jnp.take_along_axis(
                    G, jnp.maximum(rc - 1, 0)[:, None, None, None],
                    axis=2)[:, :, 0], 0.0)                       # [B, H, K]
            before = (s_idx[None, :] < rc[:, None])[:, None, :, None]
            w = jnp.exp(jnp.where(before, G_r[:, :, None, :] - G, -jnp.inf))
            S_r = (S * jnp.exp(G_r)[..., None]
                   + jnp.einsum("bhsc,bhsv->bhcv", kc * w, bU,
                                precision=_HI))
            snap = jnp.where(here[:, None, None, None], S_r, snap)
        return (S_new, snap), o

    state = state.astype(f32)
    (final, snap), os_ = jax.lax.scan(
        one, (state, state),
        (chunks(q), chunks(k), chunks(v), chunks(g), chunks(beta),
         jnp.arange(nc)))
    # [nc, B, H, Q, V] -> [B, L, H, V]
    o = jnp.moveaxis(jnp.moveaxis(os_, 0, 1), 2, 3).reshape(
        B, nc * Q, H, -1)[:, :L]
    return o, final, (snap if want else None)


def kda_recurrence(q, k, v, g, beta, state):
    """The plain recurrence, one token at a time (``lax.scan``): what
    :func:`kda_chunked` must equal.  Same arguments; returns ``(o,
    final)`` and knows no lengths."""
    f32 = jnp.float32

    def one(S, inp):
        qt, kt, vt, gt, bt = inp
        new = _update(S, kt, vt, gt, bt)
        return new, jnp.einsum("bhkv,bhk->bhv", new, qt, precision=_HI)

    final, os_ = jax.lax.scan(
        one, state.astype(f32),
        tuple(jnp.moveaxis(a.astype(f32), 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(os_, 0, 1), final


def _update(S, k, v, g, beta):
    """One token's state update, ``[B, H, ...]`` rows, float32."""
    S = S * jnp.exp(g)[..., None]
    u = v - jnp.einsum("bhkv,bhk->bhv", S, k, precision=_HI)
    return S + (beta[..., None] * k)[..., None] * u[:, :, None, :]


# -- the one-token step -------------------------------------------------------

def kda_step_reference(state, q, k, v, g, beta, live=None):
    """One token a slot: ``state [B, H, K, V]`` float32, ``q`` / ``k`` /
    ``g [B, H, K]``, ``v [B, H, V]``, ``beta [B, H]``.  Returns ``(o [B,
    H, V] float32, new state)``; a slot with ``live[b]`` false keeps its
    state and returns zeros.  Reads and writes every slot's state."""
    f32 = jnp.float32
    new = _update(state.astype(f32), k.astype(f32), v.astype(f32),
                  g.astype(f32), beta.astype(f32))
    o = jnp.einsum("bhkv,bhk->bhv", new, q.astype(f32), precision=_HI)
    if live is None:
        return o, new
    on = live[:, None, None, None]
    return jnp.where(live[:, None, None], o, 0.0), jnp.where(on, new, state)


def _step_kernel(on_ref, lead_ref, src_ref, lo_ref, hi_ref, s_ref, cols_ref,
                 v_ref, o_ref, so_ref):
    """One (slot, head block): the states ``[hb, K, V]``; the step's key-
    side vectors head-minor, ``cols [4, K, hb]`` = the decay ``exp(g)``,
    ``k``, ``beta * k`` and ``q`` (a head's column broadcasts over the
    state's lanes as it lies); the values ``[hb, V]``; ``o [hb, V]``."""
    b = pl.program_id(0)
    hb = s_ref.shape[0]

    @pl.when(on_ref[b] != 0)
    def _():
        decay, kc, bk, qc = (cols_ref[0], cols_ref[1], cols_ref[2],
                             cols_ref[3])                        # [K, hb]
        vs = v_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        out = jnp.zeros(o_ref.shape, jnp.float32)
        for i in range(hb):
            S = s_ref[i] * decay[:, i:i + 1]
            u = vs[i:i + 1] - jnp.sum(S * kc[:, i:i + 1], axis=0,
                                      keepdims=True)             # [1, V]
            new = S + bk[:, i:i + 1] * u
            so_ref[i] = new
            out = jnp.where(row == i, jnp.sum(new * qc[:, i:i + 1], axis=0,
                                              keepdims=True), out)
        o_ref[...] = out

    @pl.when(on_ref[b] == 0)
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    # free slots ahead of the first live one name ITS first block, which
    # no step has written: hand it through (``ops/ssm._step_kernel``)
    @pl.when(lead_ref[b] != 0)
    def _():
        so_ref[...] = s_ref[...]


def kda_step(state, q, k, v, g, beta, live, *, interpret=None):
    """:func:`kda_step_reference` as one Pallas call, the states updated
    in place (donate or carry ``state``: it is aliased in and out).  A
    free slot's state is neither fetched nor written back."""
    B, H, K, V = state.shape
    f32 = jnp.float32
    hb = step_block(H, K, V)
    nb = H // hb
    k = k.astype(f32)
    cols = jnp.stack([jnp.exp(g.astype(f32)), k,
                      beta.astype(f32)[..., None] * k, q.astype(f32)],
                     axis=1)                                     # [B, 4, H, K]
    # [B, 4, H, K] -> [B, nb, 4, K, hb]
    cols = jnp.moveaxis(cols.reshape(B, 4, nb, hb, K), (2, 3), (1, 4))
    vs = v.astype(f32).reshape(B, nb, hb, V)
    on, lead, src, lo, hi = ssm._step_plan(live, nb)

    def held(b, j, on, lead, src, lo, hi):
        return src[b], jnp.clip(j, lo[b], hi[b])

    def s_index(*step):
        s, j = held(*step)
        return s, j, 0, 0

    def c_index(*step):
        s, j = held(*step)
        return s, j, 0, 0, 0

    s_spec = pl.BlockSpec((None, hb, K, V), s_index)
    o, new = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(B, nb),
            in_specs=[s_spec,
                      pl.BlockSpec((None, None, 4, K, hb), c_index),
                      pl.BlockSpec((None, None, hb, V), s_index)],
            out_specs=[pl.BlockSpec((None, None, hb, V),
                                    lambda b, j, *_: (b, j, 0, 0)),
                       s_spec]),
        out_shape=[jax.ShapeDtypeStruct((B, nb, hb, V), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the five prefetched scalars
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=(not _on_tpu()) if interpret is None else interpret,
        name="kda_step",
    )(on, lead, src, lo, hi, state, cols, vs)
    return o.reshape(B, H, V), new
