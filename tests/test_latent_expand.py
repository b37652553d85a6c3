"""The expanded latent path's kernel (``ops/latent_attention.
latent_expand_tiled``) at small widths on the CPU, in Pallas interpret
mode: against the XLA loop it replaces on the chip
(``expanded_attention``) and a dense one-shot reference; the dispatch
rule (``expand_applies``), the tile of the path that runs
(``expand_block`` / ``expand_heads``), and the engine's bookkeeping of
both (``LatentRows.tile``, ``_require_fit``, ``stats()``).

Nothing selects the kernel on the CPU: every test that wants it calls
it, or patches the rule, itself.  TOLERANCE: the loop's tests' (1e-5
absolute in float32 at these widths; measured 0 to 4e-7).
"""

import ast
import dataclasses
import glob
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from edl_tpu.models.transformer import TransformerConfig, TransformerLM
from edl_tpu.ops import latent_attention as la
from edl_tpu.serving.engine import ContinuousBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEADS, RANK, NOPE, ROPE, VD = 4, 32, 16, 8, 16
ROW, TILE = 128, 128
SCALE = (NOPE + ROPE) ** -0.5


def close(got, want, tol=1e-5):
    assert np.shape(got) == np.shape(want)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def inputs(L, T, seed=0, batch=1, dtype=jnp.float32):
    """Queries, a slab of cache rows (``c | k_pe | zeros``) and
    ``W_kvb``."""
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (batch, L, HEADS, NOPE + ROPE), dtype)
    rows = la.cache_rows(
        jax.random.normal(ks[1], (batch, T, RANK + ROPE)), ROW, dtype)
    w_kvb = jax.random.normal(ks[2], (RANK, HEADS, NOPE + VD)) * RANK ** -0.5
    return q, rows, w_kvb


def plain(q, rows, w_kvb, mask):
    """Attention over keys and values expanded for every head, the
    whole slab at once."""
    f32 = jnp.float32
    kv = jnp.einsum("btc,chd->bthd", rows[..., :RANK].astype(f32), w_kvb)
    k = jnp.concatenate([kv[..., :NOPE], jnp.broadcast_to(
        rows[:, :, None, RANK:RANK + ROPE].astype(f32),
        kv.shape[:3] + (ROPE,))], -1)
    s = jnp.einsum("blhd,bthd->bhlt", q.astype(f32), k) * SCALE
    p = jax.nn.softmax(jnp.where(mask[:, None], s, -jnp.inf), -1)
    return jnp.einsum("bhlt,bthd->blhd", p, kv[..., NOPE:])


@pytest.fixture
def small_tiles(monkeypatch):
    """Budgets under the toy shapes': tiles of 128 rows, the smallest
    there is, and blocks of two of the four heads (of one for the
    widest call)."""
    monkeypatch.setattr(la, "_SCORE_BYTES", 1)
    monkeypatch.setattr(la, "_EXPAND_BYTES", 2 * TILE * (NOPE + VD) * 4)
    monkeypatch.setattr(la, "_TILE_BYTES", 1)       # the loop's, too
    assert la.expand_block(1, 8, HEADS, NOPE + VD, 512, jnp.float32,
                           True) == TILE
    assert la.expand_heads(HEADS, NOPE + VD, TILE, 8) == 2
    assert la.expand_heads(HEADS, NOPE + VD, TILE, 512) == 1


# -- the kernel against the loop and the dense reference ---------------------

# (lanes, call length, offset, real tokens of the call, traced limit)
@pytest.mark.parametrize("batch,L,offset,real,traced", [
    (1, 8, 0, 8, True),         # one sublane tile of queries, from row 0
    (1, 8, 92, 8, True),        # limit 100: inside the first tile
    (1, 8, 120, 8, True),       # limit 128: on the tile's edge
    (1, 8, 121, 8, True),       # limit 129: one row past it
    (1, 8, 292, 8, True),       # limit 300: deep in the slab
    (1, 16, 120, 16, True),     # a chunk that crosses the edge
    (2, 8, 200, 8, True),       # two lanes at a uniform index
    (3, 16, 0, 16, True),       # a cold prefill of three lanes
    (1, 16, 240, 5, True),      # a padded final chunk, the pads past an edge
    (1, 8, 504, 8, True),       # the slab's last rows
    (1, 8, 292, 8, False),      # the same limit, static
    (1, 128, 128, 128, False),  # a static limit on an edge
    (1, 512, 0, 512, True),     # the widest served chunk, four tiles
])
def test_the_kernel_equals_the_loop_and_plain_attention(
        small_tiles, batch, L, offset, real, traced):
    """Against a slab of four tiles (eight for the widest call): the
    kernel, the XLA loop and plain attention over the whole slab under
    the position mask agree; rows the call must not read are poisoned
    (every tile wholly past ``limit`` NaN, the stale rows of the last
    live tile large and finite) and the output does not move."""
    T = (4 if offset + L <= 4 * TILE else 8) * TILE
    q, rows, w_kvb = inputs(L, T, seed=offset + L, batch=batch)
    limit = offset + L
    q_pos = offset + jnp.broadcast_to(jnp.arange(L), (batch, L))
    mask = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
    want = plain(q, rows, w_kvb, mask)
    edge = -(-limit // TILE) * TILE
    poisoned = rows.at[:, limit:edge].set(3e4).at[:, edge:].set(jnp.nan)

    def call(path):
        return jax.jit(lambda rows, n: path(
            q, rows, w_kvb, q_pos, n if traced else limit, rank=RANK,
            nope=NOPE, scale=SCALE))

    kernel, loop = call(la.latent_expand_tiled), call(la.expanded_attention)
    n = jnp.asarray(limit, jnp.int32)
    for slab in (rows, poisoned):
        got = kernel(slab, n)
        assert got.dtype == q.dtype and got.shape == (batch, L, HEADS, VD)
        assert np.isfinite(np.asarray(got)).all()
        # pad queries (past ``real``) see their own garbage rows only
        close(got[:, :real], want[:, :real])
        close(got[:, :real], loop(slab, n)[:, :real])


def test_queries_at_their_own_positions_a_lane(small_tiles):
    """``q_pos`` is a lane's own: two lanes whose calls start at
    different rows, under one ``limit``."""
    L, T = 16, 4 * TILE
    q, rows, w_kvb = inputs(L, T, seed=5, batch=2)
    q_pos = jnp.asarray([[3], [250]]) + jnp.arange(L)
    mask = jnp.arange(T)[None, None, :] <= q_pos[:, :, None]
    got = la.latent_expand_tiled(q, rows, w_kvb, q_pos, 250 + L, rank=RANK,
                                 nope=NOPE, scale=SCALE)
    close(got, plain(q, rows, w_kvb, mask))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_output_is_the_queries_dtype_at_the_loops_precision(
        small_tiles, dtype):
    """bfloat16 rows and queries: the expansion rounds to the cache's
    dtype and the probabilities are cast to it before the second matmul,
    as the loop does, so the two agree to bfloat16's last place."""
    dtype = jnp.dtype(dtype)
    L, T, offset = 16, 4 * TILE, 200
    q, rows, w_kvb = inputs(L, T, seed=9, dtype=dtype)
    q_pos = offset + jnp.broadcast_to(jnp.arange(L), (1, L))
    args = (q, rows, w_kvb, q_pos, offset + L)
    got = la.latent_expand_tiled(*args, rank=RANK, nope=NOPE, scale=SCALE)
    want = la.expanded_attention(*args, rank=RANK, nope=NOPE, scale=SCALE)
    assert got.dtype == want.dtype == dtype
    close(got, want, 2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_the_rows_padding_is_masked_not_trusted(small_tiles):
    """The queries' zero padding meets the rest of a row past ``k_pe``:
    the kernel zeroes that rest itself, so a slab whose padding is not
    zeros (NaN here) gives the same output."""
    L, T = 8, 2 * TILE
    q, rows, w_kvb = inputs(L, T, seed=2)
    q_pos = 100 + jnp.broadcast_to(jnp.arange(L), (1, L))
    args = (w_kvb, q_pos, 100 + L)
    want = la.latent_expand_tiled(q, rows, *args, rank=RANK, nope=NOPE,
                                  scale=SCALE)
    dirty = rows.at[..., RANK + ROPE:].set(jnp.nan)
    close(la.latent_expand_tiled(q, dirty, *args, rank=RANK, nope=NOPE,
                                 scale=SCALE), want)


# -- a decode model's multi-token calls through it ----------------------------

def toy_config(rope: bool, **kw):
    return TransformerConfig(
        vocab_size=64, num_layers=2, embed_dim=32, num_heads=HEADS,
        mlp_dim=64, max_len=256, dtype=jnp.float32, remat=False,
        attention_impl="dense", layer_attn=("latent", "latent"),
        mla_rank=RANK, mla_nope_dim=NOPE, mla_rope_dim=ROPE, mla_v_dim=VD,
        mla_rope=rope, **kw)


def force_kernel(monkeypatch, sublanes=8):
    """Take the kernel wherever its shape rule holds, TPU or not
    (interpret mode here)."""
    monkeypatch.setattr(
        la, "expand_applies",
        lambda L, mesh, T, row, dtype, *widths: (
            mesh is None and L > 1 and L % sublanes == 0
            and not any(n % 128 for n in (T, row))))


@pytest.mark.parametrize("rope", [True, False], ids=["rotated", "nope"])
def test_chunks_through_the_cache_equal_the_loops(monkeypatch, small_tiles,
                                                  rope):
    """A decode model prefills 136 tokens as a chunk of 128 and one of 8
    (the second against the first's rows, across a tile's edge), with
    rotated shared key dims (openPangu's rows) and without (Kimi-Linear's
    NoPE rows): the logits through the kernel equal the loop's."""
    cfg = toy_config(rope)
    ids = jax.random.randint(jax.random.key(1), (2, 136), 1, 64)
    model = TransformerLM(dataclasses.replace(cfg, decode=True))
    params = model.init(jax.random.key(0), ids[:, :4])["params"]

    def chunks():
        cache = model.init(jax.random.key(0), ids[:, :1])["cache"]
        out = []
        for lo, hi in ((0, 128), (128, 136)):
            pos = jnp.broadcast_to(jnp.arange(lo, hi), (2, hi - lo))
            logits, mut = model.apply(
                {"params": params, "cache": cache}, ids[:, lo:hi],
                positions=pos, mutable=["cache"])
            cache = mut["cache"]
            out.append(logits)
        return jnp.concatenate(out, 1)

    want = chunks()
    force_kernel(monkeypatch)
    called = []
    tiled = la.latent_expand_tiled
    monkeypatch.setattr(la, "latent_expand_tiled",
                        lambda *a, **k: called.append(1) or tiled(*a, **k))
    close(chunks(), want, 1e-4)
    assert len(called) == 2 * 2          # two chunks x two layers
    # the forward that is not decoding stays the loop whatever the rule
    # says (it is differentiated; the kernel has no gradient)
    plain_model = TransformerLM(cfg)
    plain_model.apply(plain_model.init(jax.random.key(0), ids), ids)
    assert len(called) == 4


# -- the rule -----------------------------------------------------------------

SERVED = (32768, 640, jnp.bfloat16, 512, 128, 128)   # T, row, dtype, widths


def test_the_rule_is_false_on_the_cpu():
    """Every CPU test runs the code it ran: nothing here is a TPU."""
    assert not la.expand_applies(512, None, *SERVED)
    assert not toy_config(True).mla_tiled(16)


@pytest.mark.parametrize("L,mesh,T,row,widths,want", [
    (512, None, 32768, 640, (512, 128, 128), True),    # openPangu's chunk
    (256, None, 32768, 640, (512, 128, 128), True),    # Kimi-Linear's
    (16, None, 32768, 640, (512, 128, 128), True),     # one sublane tile
    (512, "mesh", 32768, 640, (512, 128, 128), False),
    (1, None, 32768, 640, (512, 128, 128), False),     # the absorbed path's
    (24, None, 32768, 640, (512, 128, 128), False),    # not sublane tiles
    (8192, None, 32768, 640, (512, 128, 128), False),  # scores over budget
    (512, None, 32768 + 64, 640, (512, 128, 128), False),
    (512, None, 32768, 576, (512, 128, 128), False),   # the row as computed
    (512, None, 32768, 640, (448, 128, 128), False),   # a rank the kernel
    (512, None, 32768, 640, (512, 64, 128), False),    # cannot slice at
    (512, None, 32768, 640, (512, 128, 64), False),
])
def test_the_rule_is_shape_mesh_and_backend_only(monkeypatch, L, mesh, T,
                                                 row, widths, want):
    monkeypatch.setattr(la, "_on_tpu", lambda: True)
    assert la.expand_applies(L, mesh, T, row, jnp.bfloat16, *widths) is want
    # float32 rows tile by 8 sublanes: 24 queries are whole tiles there
    if L == 24:
        assert la.expand_applies(L, mesh, T, row, jnp.float32, *widths)


@pytest.mark.parametrize("L,H,T,rows,heads", [
    (512, 128, 32768, 1024, 4),      # openPangu's chunk (the chip's sweep)
    (256, 32, 32768, 1024, 4),       # Kimi-Linear's
    (256, 128, 32768, 1024, 4),      # openPangu's bucketed prefill
    (1024, 128, 32768, 512, 4),      # longer calls: the scores bound it
    (2048, 128, 32768, 256, 2),      # and the resident queries the block
    (16, 2, 32768, 1024, 2),         # never more heads than there are
    (512, 128, 3 * 512, 512, 8),     # divides T
    (512, 128, 96, 96, 8),           # not lane tiles: one tile (the loop's)
])
def test_the_kernels_tile_follows_the_shape(L, H, T, rows, heads):
    """Rows a tile and heads a block: powers of two within the VMEM
    budgets; lanes do not count (a grid step holds one)."""
    bf = jnp.bfloat16
    for lanes in (1, 8):
        assert la.expand_block(lanes, L, H, 256, T, bf, True) == rows
    assert la.expand_heads(H, 256, rows, L) == heads
    assert H % heads == 0
    # the loop's tile is what it was
    assert la.expand_block(1, 512, 128, 256, 32768, bf) == 128
    assert la.expand_block(1, 256, 32, 256, 32768, bf, False) == 1024


def test_the_kernels_name_is_no_metrics_kernel():
    """``latent_expand_tiled`` must not be summed into the one-token
    kernels' rooflines (or any other's): every ``KERNEL`` pattern under
    ``benchmarks/layer_metrics/``, read from the files."""
    traced = str(jax.make_jaxpr(lambda q, rows, w: la.latent_expand_tiled(
        q, rows, w, jnp.zeros((1, 8), jnp.int32), 8, rank=RANK, nope=NOPE,
        scale=SCALE))(*inputs(8, TILE)))
    name = "latent_expand_tiled"
    assert "pallas_call" in traced and name in traced
    patterns = {}
    for path in sorted(glob.glob(os.path.join(
            ROOT, "benchmarks", "layer_metrics", "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in tree.body:      # KERNEL = re.compile(...), evaluated
            if (isinstance(node, ast.Assign)
                    and getattr(node.targets[0], "id", "") == "KERNEL"):
                patterns[os.path.basename(path)] = eval(compile(
                    ast.Expression(node.value), path, "eval"), {"re": re})
    assert len(patterns) >= 7
    assert "mla_attend_roofline.py" in patterns
    assert [f for f, p in patterns.items() if p.search(name)] == []
    assert patterns["mla_attend_roofline.py"].search("latent_attend")


# -- the engine's bookkeeping --------------------------------------------------

@pytest.fixture(scope="module")
def toy_params():
    cfg = toy_config(True)
    return TransformerLM(cfg).init(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]


def engine(params, **kw):
    kw = dict(dict(slots=3, max_len=256, temperature=0.0, steps_per_sync=4,
                   kv_block=8, kv_pool_blocks=96, prefill_chunk=16,
                   prefill_buckets=(4, 16)), **kw)
    return ContinuousBatcher(toy_config(True), params, **kw)


def serve(eng, prompt, n):
    return eng.submit(np.asarray(prompt, np.int32), n).result(300).tolist()


PROMPTS = [list(range(1, 4)), list(range(5, 46)), list(range(7, 19))]


def test_the_engine_counts_the_calls_that_took_the_kernel(
        monkeypatch, toy_params):
    """A served trace under a rule that admits 16-token calls and not
    4-token ones: a 3-token prompt (one bucket of 4: the loop), 41
    tokens through the chunk lane (16, 16, a last bucket of 16: the
    kernel), 12 tokens (a bucket of 16).  ``latent_prefill_calls`` and
    ``latent_prefill_kernel_calls`` are the host's recount, a lane and
    latent layer; the tokens are the loop's."""
    def run():
        eng = engine(toy_params)
        try:
            return [serve(eng, p, 5) for p in PROMPTS], eng.stats()
        finally:
            eng.stop()

    monkeypatch.setattr(la, "_SCORE_BYTES", 1)   # the kernel's tile: 128
    want, off = run()
    force_kernel(monkeypatch, sublanes=16)
    got, on = run()
    assert got == want
    layers = 2
    for s in (off, on):
        assert s["latent_prefill_calls"] == layers * (1 + 3 + 1)
        assert s["latent_prefill_tokens"] == 3 + 41 + 12
    assert off["latent_prefill_kernel_calls"] == 0
    assert on["latent_prefill_kernel_calls"] == layers * (3 + 1)
    # the rows read follow the tile of the path each call ran: the
    # kernel's 128 rows here, the loop's one tile of the whole slab
    assert off["latent_prefill_rows_read"] == layers * 5 * 256
    assert on["latent_prefill_rows_read"] == layers * (256 + 4 * TILE)
    assert on["latent_prefill_rows_live"] == off["latent_prefill_rows_live"]


@pytest.mark.parametrize("kernel", [False, True], ids=["loop", "kernel"])
def test_the_fit_prices_the_path_that_runs(monkeypatch, toy_params, kernel):
    """``LatentRows.tile`` is ``expand_block`` of the path the call takes,
    and ``_require_fit`` (through a device that reports a limit) prices
    a lane's attention at it: the loop's tile of float32 scores,
    probabilities and expanded rows, or the kernel's head-major queries
    and output (its tile never leaves the chip).  The smallest limit
    that keeps the whole ladder moves by exactly that difference."""
    class _Chip:
        device_kind = "toy chip"
        limit = 1 << 40

        def memory_stats(self):
            return {"bytes_limit": self.limit, "bytes_in_use": 0}

    def least_limit(eng):
        """The smallest device that keeps the ladder's widest rung."""
        chip, rungs = _Chip(), eng.PREFILL_KS
        monkeypatch.setattr(jax, "devices", lambda: [chip])
        lo, hi = 0, 1 << 40
        while hi - lo > 1:
            chip.limit = (lo + hi) // 2
            eng.PREFILL_KS = rungs
            try:
                eng._require_fit(3, 8, 96, 0)
                ok = eng.PREFILL_KS == rungs
            except Exception:  # noqa: BLE001 - refused outright: too small
                ok = False
            lo, hi = (lo, chip.limit) if ok else (chip.limit, hi)
        eng.PREFILL_KS = rungs
        return hi

    eng = engine(toy_params)
    try:
        rungs, p_max, kv = eng.PREFILL_KS, 16, NOPE + VD
        loop_limit = least_limit(eng)
        tile = eng._classes["layer_0"].tile
        assert tile(rungs[0], p_max, HEADS) == la.expand_block(
            rungs[0], p_max, HEADS, kv, 256, jnp.float32) == 256
        if kernel:
            force_kernel(monkeypatch)
            assert tile(rungs[0], p_max, HEADS) == la.expand_block(
                rungs[0], p_max, HEADS, kv, 256, jnp.float32, True)
            loop = 256 * HEADS * (2 * 4 * p_max + kv * 4)
            tiled = p_max * HEADS * (kv + ROW - RANK) * 4
            assert least_limit(eng) - loop_limit == rungs[0] * (tiled - loop)
        else:
            assert not eng._dcfg.mla_tiled(p_max)
            assert least_limit(eng) == loop_limit
    finally:
        eng.stop()
