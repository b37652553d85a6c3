"""``ops/kda.py`` and ``ops/latent_attention.py`` at small sizes on the
CPU: the chunked delta rule against the token-by-token recurrence, the
one-token kernel (Pallas interpret mode) against its einsum reference,
and the latent attention's two paths and two kernels against each other
and against plain attention over expanded keys and values.

TOLERANCE: float32 on both sides but not the same sums (a triangular
solve a chunk against a rank-one update a token): 2e-5 relative to the
largest reference magnitude; measured 1e-7 to 3e-6.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from edl_tpu.ops import kda
from edl_tpu.ops import latent_attention as la

RTOL = 2e-5
B, H, K, V = 2, 3, 8, 8


def close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    err = np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)
    assert err <= rtol, f"relative error {err:.2e} over {rtol:.0e}"


def inputs(L, seed=0, batch=B):
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, L, H, K))) * K ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, L, H, K)))
    v = jax.random.normal(ks[2], (batch, L, H, V))
    # decays from a channel that forgets in a token to one that hardly does
    g = -jnp.exp(jax.random.uniform(ks[3], (batch, L, H, K), minval=-6.0,
                                    maxval=1.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, L, H)))
    state = jax.random.normal(ks[5], (batch, H, K, V))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("L,chunk", [(1, 8), (7, 8), (8, 8), (16, 8),
                                     (29, 8), (29, 64), (40, 16)])
def test_chunked_equals_the_recurrence_from_a_state(L, chunk):
    args = inputs(L)
    want_o, want_s = kda.kda_recurrence(*args)
    o, final, snap = kda.kda_chunked(*args, chunk=chunk)
    assert snap is None
    close(o, want_o)
    close(final, want_s)


def test_a_vanished_channel_divides_nothing():
    """A channel whose decay underflows inside a chunk (exp(-80 * 8) is
    0 in float32): differences of the running sum stay finite."""
    q, k, v, g, beta, state = inputs(16, seed=3)
    g = g.at[:, :, 0, 0].set(-80.0)
    want_o, want_s = kda.kda_recurrence(q, k, v, g, beta, state)
    o, final, _ = kda.kda_chunked(q, k, v, g, beta, state, chunk=8)
    assert np.isfinite(np.asarray(o)).all()
    close(o, want_o)
    close(final, want_s)


def test_a_padded_lanes_state_is_its_unpadded_runs():
    L, lengths = 21, jnp.asarray([21, 13])
    args = inputs(L, seed=1)
    o, final, _ = kda.kda_chunked(*args, chunk=8, lengths=lengths)
    for b, n in enumerate([21, 13]):
        one = tuple(a[b:b + 1, :n] for a in args[:5]) + (args[5][b:b + 1],)
        want_o, want_s = kda.kda_recurrence(*one)
        close(o[b:b + 1, :n], want_o)
        close(final[b:b + 1], want_s)


@pytest.mark.parametrize("snap_at", [0, 1, 5, 8, 16, 17, 24, 29, 40])
def test_snap_at_is_the_state_after_that_many_tokens(snap_at):
    L, lengths = 29, jnp.asarray([29, 24])
    args = inputs(L, seed=2)
    at = jnp.asarray([snap_at, snap_at])
    _, final, snap = kda.kda_chunked(*args, chunk=8, lengths=lengths,
                                     snap_at=at)
    for b, n in enumerate([29, 24]):
        upto = min(snap_at, n)
        one = tuple(a[b:b + 1, :upto] for a in args[:5]) + (args[5][b:b + 1],)
        want = (kda.kda_recurrence(*one)[1] if upto else args[5][b:b + 1])
        close(snap[b:b + 1], want)


def step_inputs(seed=0, slots=5, heads=4):
    ks = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(ks[0], (slots, heads, 128, 128)),
            jax.random.normal(ks[1], (slots, heads, 128)) * 0.1,
            jax.random.normal(ks[2], (slots, heads, 128)) * 0.1,
            jax.random.normal(ks[3], (slots, heads, 128)),
            -jnp.exp(jax.random.normal(ks[4], (slots, heads, 128))),
            jax.nn.sigmoid(jax.random.normal(ks[5], (slots, heads))))


@pytest.mark.parametrize("live", [[1, 1, 1, 1, 1], [0, 1, 0, 1, 0],
                                  [0, 0, 1, 1, 0], [1, 0, 0, 0, 0],
                                  [0, 0, 0, 0, 0]])
def test_the_step_kernel_is_its_reference_and_leaves_free_slots(live):
    state, q, k, v, g, beta = step_inputs()
    live = jnp.asarray(live, bool)
    want_o, want_s = kda.kda_step_reference(state, q, k, v, g, beta, live)
    o, new = kda.kda_step(state, q, k, v, g, beta, live, interpret=True)
    close(o, want_o, 1e-5)
    close(new, want_s, 1e-5)
    free = ~np.asarray(live)
    # bit for bit: a free slot's state is what it was, its output zeros
    np.testing.assert_array_equal(np.asarray(new)[free],
                                  np.asarray(state)[free])
    assert (np.asarray(o)[free] == 0).all()


def test_the_step_is_one_token_of_the_recurrence():
    state, q, k, v, g, beta = step_inputs(seed=4)
    want_o, want_s = kda.kda_recurrence(
        q[:, None], k[:, None], v[:, None], g[:, None], beta[:, None], state)
    o, new = kda.kda_step_reference(state, q, k, v, g, beta)
    close(o, want_o[:, 0])
    close(new, want_s)


def test_slots_fetched_counts_live_slots():
    for live, want in (([1, 1, 0, 1], 3), ([0, 0, 1, 0], 1)):
        got = kda.slots_fetched(jnp.asarray(live, bool), 32, 128, 128)
        assert float(got) == want


# -- latent attention -----------------------------------------------------------

HEADS, RANK, NOPE, ROPE, VD = 4, 16, 8, 4, 8
W = RANK + ROPE


def latent_inputs(L, T, seed=0, batch=2):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (batch, L, HEADS, NOPE + ROPE))
    latent = jax.random.normal(ks[1], (batch, T, W))
    w_kvb = jax.random.normal(ks[2], (RANK, HEADS, NOPE + VD)) * RANK ** -0.5
    return q, latent, w_kvb


def plain(q, latent, w_kvb, mask, scale):
    """Attention over keys and values expanded for every head."""
    kv = jnp.einsum("btc,chd->bthd", latent[..., :RANK], w_kvb)
    k = jnp.concatenate([kv[..., :NOPE], jnp.broadcast_to(
        latent[:, :, None, RANK:W], kv.shape[:3] + (ROPE,))], -1)
    s = jnp.einsum("blhd,bthd->bhlt", q, k) * scale
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhlt,bthd->blhd", p, kv[..., NOPE:])


def test_expanded_equals_absorbed_equals_plain_attention():
    T, scale = 24, (NOPE + ROPE) ** -0.5
    q, latent, w_kvb = latent_inputs(1, T)
    lengths = jnp.asarray([24, 9])
    mask = (jnp.arange(T)[None, None, :] < lengths[:, None, None])
    want = plain(q, latent, w_kvb, mask, scale)
    close(la.expanded_attention(q, latent, w_kvb, lengths[:, None] - 1, T,
                                rank=RANK, nope=NOPE, scale=scale), want)
    row = la.padded_width(W)
    rows = la.cache_rows(latent, row, jnp.float32)
    q_lat = la.absorb(q[:, 0], w_kvb, nope=NOPE, width=row)
    o_lat = la.latent_attend_reference(q_lat, rows, lengths, scale=scale)
    close(la.unabsorb(o_lat, w_kvb, rank=RANK, nope=NOPE), want[:, 0])


def test_the_tile_follows_the_shape():
    """The largest power-of-two multiple of 128 rows that divides the
    slab and keeps a tile's float32 scores and expanded rows within the
    budget; a slab that is not whole lane tiles is one tile."""
    bf, T = jnp.bfloat16, 32768
    tile = lambda lanes, L, T=T: la.expand_block(lanes, L, 32, 256, T, bf)  # noqa: E731
    rows = tile(1, 256)                         # the chunk lane's shape
    assert rows >= 128 and rows & (rows - 1) == 0
    assert rows * 32 * (4 * 256 + 256 * 2) <= la._TILE_BYTES < (
        2 * rows * 32 * (4 * 256 + 256 * 2))
    assert tile(2, 256) == rows // 2 and tile(4, 256) == rows // 4
    assert tile(1, 256, 3 * rows) == rows                  # divides T
    assert tile(1 << 20, 256) == 128                       # never under
    # the expanded rows are a third of that tile's bytes, and count
    # however short the call is
    assert tile(1, 1) == tile(1, 64) == 2 * rows
    assert la.expand_block(2, 1, 4, 16, 96, bf) == 96
    assert la.expand_block(1, 1, 1, 1, 4096, bf) == 4096   # at most T


TILE = 128


@pytest.fixture
def tiles_of_128(monkeypatch):
    """The budget under one row of the toy shapes' scores: tiles of 128
    rows, the smallest there is."""
    monkeypatch.setattr(la, "_TILE_BYTES", 1)


# (lanes, call length, offset, real tokens of the call, traced limit)
@pytest.mark.parametrize("batch,L,offset,real,traced", [
    (1, 8, 92, 8, True),        # limit 100: inside the first tile
    (1, 8, 120, 8, True),       # limit 128: at the tile's edge
    (1, 8, 121, 8, True),       # limit 129: one row past it
    (1, 8, 292, 8, True),       # limit 300: not a multiple of the tile
    (1, 16, 120, 16, True),     # a chunk that crosses the edge
    (2, 8, 200, 8, True),       # two lanes at a uniform index
    (1, 16, 240, 5, True),      # a padded final chunk, the pads past an edge
    (1, 8, 504, 8, True),       # the slab's last rows
    (1, 8, 292, 8, False),      # the same limit, a static trip count
    (2, 16, 0, 16, True),       # a cold prefill from index 0
])
def test_the_tiled_path_reads_the_rows_it_can_see_and_no_others(
        tiles_of_128, batch, L, offset, real, traced):
    """The expanded path against a slab of four tiles holds plain
    attention over the whole slab under the position mask; and the rows
    it must not read are poisoned: every tile wholly past ``limit`` is
    NaN, and the stale rows of the last live tile (past the call's own)
    are large and finite (the mask multiplies them by an exact zero).
    The output does not move."""
    T, scale = 4 * TILE, (NOPE + ROPE) ** -0.5
    q, latent, w_kvb = latent_inputs(L, T, seed=offset + L, batch=batch)
    limit = offset + L
    q_pos = offset + jnp.broadcast_to(jnp.arange(L), (batch, L))
    mask = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
    want = plain(q, latent, w_kvb, mask, scale)
    edge = -(-limit // TILE) * TILE             # whole tiles up to it
    poisoned = latent.at[:, limit:edge].set(3e4).at[:, edge:].set(jnp.nan)
    attend = jax.jit(
        lambda rows, n: la.expanded_attention(
            q, rows, w_kvb, q_pos, n if traced else limit, rank=RANK,
            nope=NOPE, scale=scale))
    for rows in (latent, poisoned):
        got = attend(rows, jnp.asarray(limit, jnp.int32))
        assert np.isfinite(np.asarray(got)).all()
        # pad queries (past ``real``) see their own garbage rows only
        close(got[:, :real], want[:, :real])
    hlo = attend.lower(latent, jnp.asarray(limit, jnp.int32)).as_text()
    assert ("stablehlo.while" in hlo) == (traced or edge > TILE)


@pytest.mark.parametrize("L", [24, TILE, 3 * TILE])
def test_a_forward_that_is_not_decoding_is_the_same_function(tiles_of_128, L):
    """``T == L``, causal, a static trip count over the call's own rows
    (one pass where they fit a tile): values and gradients equal plain
    attention's."""
    scale = (NOPE + ROPE) ** -0.5
    q, latent, w_kvb = latent_inputs(L, L, seed=L)
    q_pos = jnp.broadcast_to(jnp.arange(L), (2, L))
    mask = jnp.arange(L)[None, None, :] <= q_pos[:, :, None]

    def tiled(q, latent, w_kvb):
        return la.expanded_attention(q, latent, w_kvb, q_pos, L, rank=RANK,
                                     nope=NOPE, scale=scale)

    close(tiled(q, latent, w_kvb), plain(q, latent, w_kvb, mask, scale))
    loss = lambda f: lambda *a: (f(*a) ** 2).sum()           # noqa: E731
    got = jax.grad(loss(tiled), argnums=(0, 1, 2))(q, latent, w_kvb)
    want = jax.grad(loss(lambda *a: plain(*a, mask, scale)),
                    argnums=(0, 1, 2))(q, latent, w_kvb)
    for g, w in zip(got, want):
        close(g, w, 1e-4)
    assert ("while" in jax.jit(tiled).lower(q, latent, w_kvb).as_text()
            ) == (L > TILE)


@pytest.mark.parametrize("lengths", [[256, 100, 0, 129], [0, 0, 0, 0],
                                     [1, 128, 256, 255]])
def test_the_attend_kernel_is_its_reference(lengths):
    T, row = 256, 128
    ks = jax.random.split(jax.random.key(5), 2)
    q_lat = jax.random.normal(ks[0], (4, HEADS, row))
    rows = jax.random.normal(ks[1], (4, T, row))
    lengths = jnp.asarray(lengths)
    want = la.latent_attend_reference(q_lat, rows, lengths, scale=0.2)
    got = la.latent_attend(q_lat, rows, lengths, scale=0.2, block=128,
                           interpret=True)
    close(got, want, 1e-5)
    assert (np.asarray(got)[np.asarray(lengths) == 0] == 0).all()


def test_the_append_kernel_is_its_reference():
    ks = jax.random.split(jax.random.key(6), 2)
    rows = jax.random.normal(ks[0], (4, 64, 128)).astype(jnp.bfloat16)
    new = jax.random.normal(ks[1], (4, 128))
    index = jnp.asarray([0, 17, 63, 64])
    live = jnp.asarray([True, False, True, True])
    want = la.latent_append_reference(rows, new, index, live)
    got = la.latent_append(rows, new, index, live, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # a free slot and a write past the end leave their rows as they were
    np.testing.assert_array_equal(np.asarray(got)[[1, 3]],
                                  np.asarray(rows)[[1, 3]])
    assert (np.asarray(got)[0, 0] == np.asarray(new.astype(jnp.bfloat16))[0]
            ).all()


def test_tokens_fetched_counts_whole_tiles_of_live_slots():
    lengths = jnp.asarray([300, 0, 512, 1])
    # tiles of 512 at this width: one tile each for 300, 512 and 1
    assert la.attend_block(640, 2048, jnp.bfloat16) == 512
    got = la.tokens_fetched(lengths, 640, 2048, jnp.bfloat16, kernel=True)
    assert float(got) == 3 * 512
    assert float(la.tokens_fetched(lengths, 640, 2048, jnp.bfloat16,
                                   kernel=False)) == 4 * 2048
