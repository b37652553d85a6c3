"""The plain reference: the decoder's forward pass and loss in float32.

Straight ``jax.numpy``, ``jax.default_matmul_precision("highest")``, no
kernels, no cache, no batching tricks; independent of
``edl_tpu/models``.  It follows the published Mistral block (RMSNorm,
grouped-query attention with RoPE, gated SiLU MLP, untied head) with
two departures that follow the PROGRAM, so that one set of weights
serves both: RMSNorm's epsilon is the program's 1e-6 (the model card's
is 1e-5) and RoPE rotates interleaved pairs (x[2i], x[2i+1]) where the
published code rotates half-split pairs - the same function up to a
fixed permutation of the random weights.

Takes the program's parameter tree (stacked ``layers`` or split
``layer_i``) in whatever type it is stored in and computes in float32,
one layer per jitted call so that only one layer's float32 copy lives
at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EPS = 1e-6
Q_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, scale):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * _f32(scale)


def _rope(x, theta):
    # x: [B, L, H, D]; pairs (2i, 2i+1) rotated by pos * theta^(-2i/D)
    d = x.shape[-1]
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None] * freqs[None, :]                    # [L, D/2]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "theta"))
def _layer(x, p, *, heads, kv_heads, theta):
    with jax.default_matmul_precision("highest"):
        b, l, d = x.shape
        dh = d // heads
        y = _rmsnorm(x, p["attn_norm"]["scale"])
        qkv = y @ _f32(p["attn_qkv"]["kernel"])
        q, k, v = jnp.split(qkv, [heads * dh, (heads + kv_heads) * dh], -1)
        q = _rope(q.reshape(b, l, heads, dh), theta)
        k = _rope(k.reshape(b, l, kv_heads, dh), theta)
        v = v.reshape(b, l, kv_heads, dh)
        g = heads // kv_heads
        k = jnp.repeat(k, g, axis=2)       # q head h reads kv head h // g
        v = jnp.repeat(v, g, axis=2)

        def attend(args):
            # one block of queries against the whole context: the same
            # mathematics as the full [L, L] score matrix, a block of
            # rows at a time so that it fits beside a train state
            qb, start = args
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * dh ** -0.5
            rows = start + jnp.arange(qb.shape[1])
            s = jnp.where(rows[:, None] >= jnp.arange(l)[None, :], s,
                          -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

        nb = l // Q_BLOCK if l > Q_BLOCK and l % Q_BLOCK == 0 else 1
        qs = q.reshape(b, nb, l // nb, heads, dh).swapaxes(0, 1)
        a = jax.lax.map(attend, (qs, jnp.arange(nb) * (l // nb)))
        a = a.swapaxes(0, 1).reshape(b, l, heads, dh)
        x = x + a.reshape(b, l, heads * dh) @ _f32(p["attn_out"]["kernel"])
        y = _rmsnorm(x, p["mlp_norm"]["scale"])
        gate = jax.nn.silu(y @ _f32(p["mlp_gate"]["kernel"]))
        up = y @ _f32(p["mlp_in"]["kernel"])
        return x + (gate * up) @ _f32(p["mlp_out"]["kernel"])


@jax.jit
def _head(x, norm_scale, w):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_scale) @ _f32(w)


def _layers(params, n):
    if "layers" in params:
        return [jax.tree.map(lambda a: a[i], params["layers"])
                for i in range(n)]
    return [params[f"layer_{i}"] for i in range(n)]


def hidden(conf: dict, params, ids):
    """Final hidden states [B, L, D] before the last norm."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    for p in _layers(params, conf["num_hidden_layers"]):
        x = _layer(x, p, heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   theta=float(conf["rope_theta"]))
    return x


def logits(conf: dict, params, ids):
    """[B, L, V] float32 logits of the full forward pass."""
    if conf.get("tie_word_embeddings"):
        w = params["tok_embed"]["embedding"].T
    else:
        w = params["lm_head"]["kernel"]
    return _head(hidden(conf, params, ids), params["final_norm"]["scale"], w)


def loss(conf: dict, params, ids):
    """Mean next-token cross entropy over ``ids`` [B, L+1], one
    sequence at a time (a [L, V] float32 logits block each)."""
    total = 0.0
    for row in ids:
        lg = logits(conf, params, row[None, :-1])[0]
        lp = jax.nn.log_softmax(lg, -1)
        total += float(-jnp.take_along_axis(
            lp, jnp.asarray(row[1:])[:, None], -1).mean())
    return total / len(ids)
