"""The plain reference of the published OLMoE layer, tier-1 copy.

A copy of the reference in ``benchmarks/archs/olmoe.py`` (the
benchmark's files are its own yardstick and import nothing from
``tests/``; ``tests/test_olmoe.py`` holds the two equal on seeded
inputs).  Straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, no kernels, no cache, no
sort: every expert is applied to every token and weighted by a dense
``[tokens, experts]`` matrix that is zero where the token did not
choose the expert.  Independent of ``edl_tpu/``.  ``conf`` carries the
published key names (``config.json``); epsilon is the file's
``rms_norm_eps`` (1e-5 as published), ``norm_topk_prob`` false as
published.  Two departures, both a fixed permutation of random weights:
RoPE rotates interleaved pairs (x[2i], x[2i+1]) where the published
code rotates half-split pairs, and q, k, v come from one fused
``attn_qkv`` matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

Q_BLOCK = 512


def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


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


def _moe(y, p, *, top_k, norm_topk):
    """The published sparse block on ``y [T, D]``: float32 softmax over
    the experts, the ``top_k`` largest kept (renormalised only when
    ``norm_topk``), every expert's gated SiLU FFN weighted by what the
    token gave it.  Returns ``(out [T, D], chosen [T, top_k])``."""
    probs = jax.nn.softmax(y @ _f32(p["gate"]), axis=-1)       # [T, E]
    vals, chosen = jax.lax.top_k(probs, top_k)
    if norm_topk:
        vals = vals / vals.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(y.shape[0])[:, None], chosen].set(vals)     # [T, E]

    def expert(acc, e):
        w_gate, w_in, w_out, w = e
        h = jax.nn.silu(y @ _f32(w_gate)) * (y @ _f32(w_in))
        return acc + (h @ _f32(w_out)) * w[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          (p["w_gate"], p["w_in"], p["w_out"], weight.T))
    return out, chosen


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "theta", "eps", "top_k", "norm_topk"))
def _layer(x, p, *, heads, kv_heads, theta, eps, top_k, norm_topk):
    with jax.default_matmul_precision("highest"):
        b, l, d = x.shape
        dh = d // heads
        y = _rmsnorm(x, p["attn_norm"]["scale"], eps)
        qkv = y @ _f32(p["attn_qkv"]["kernel"])
        q, k, v = jnp.split(qkv, [heads * dh, (heads + kv_heads) * dh], -1)
        # q_norm / k_norm: over the whole projection, before the heads
        q = _rmsnorm(q, p["q_norm"]["scale"], eps)
        k = _rmsnorm(k, p["k_norm"]["scale"], eps)
        q = _rope(q.reshape(b, l, heads, dh), theta)
        k = _rope(k.reshape(b, l, kv_heads, dh), theta)
        v = v.reshape(b, l, kv_heads, dh)
        g = heads // kv_heads
        k = jnp.repeat(k, g, axis=2)       # q head h reads kv head h // g
        v = jnp.repeat(v, g, axis=2)

        def attend(args):
            # one block of queries against the whole context
            qb, start = args
            s = jnp.einsum("bqhd,bkhd->bhqk", qb, k) * dh ** -0.5
            rows = start + jnp.arange(qb.shape[1])
            s = jnp.where(rows[:, None] >= jnp.arange(l)[None, :], s,
                          -jnp.inf)
            return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

        nb = l // Q_BLOCK if l > Q_BLOCK and l % Q_BLOCK == 0 else 1
        qs = q.reshape(b, nb, l // nb, heads, dh).swapaxes(0, 1)
        a = jax.lax.map(attend, (qs, jnp.arange(nb) * (l // nb)))
        a = a.swapaxes(0, 1).reshape(b, l, heads * dh)
        x = x + a @ _f32(p["attn_out"]["kernel"])
        y = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
        out, chosen = _moe(y.reshape(b * l, d), p["moe"], top_k=top_k,
                           norm_topk=norm_topk)
        return x + out.reshape(b, l, d), chosen.reshape(b, l, top_k)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_scale, w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_scale, eps) @ _f32(w)


def _layers(params, n):
    if "layers" in params:
        return [jax.tree.map(lambda a: a[i], params["layers"])
                for i in range(n)]
    return [params[f"layer_{i}"] for i in range(n)]


def forward(conf: dict, params, ids):
    """``(hidden [B, L, D] before the last norm, chosen [layers, B, L,
    top_k])``: the final hidden states and every layer's expert choice."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    routes = []
    for p in _layers(params, conf["num_hidden_layers"]):
        x, chosen = _layer(
            x, p, heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"],
            theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
            top_k=conf["num_experts_per_tok"],
            norm_topk=bool(conf["norm_topk_prob"]))
        routes.append(chosen)
    return x, jnp.stack(routes)


def hidden(conf: dict, params, ids):
    """Final hidden states [B, L, D] before the last norm."""
    return forward(conf, params, ids)[0]


def logits(conf: dict, params, ids):
    """[B, L, V] float32 logits of the full forward pass."""
    if conf.get("tie_word_embeddings"):
        w = params["tok_embed"]["embedding"].T
    else:
        w = params["lm_head"]["kernel"]
    return _head(hidden(conf, params, ids), params["final_norm"]["scale"], w,
                 eps=float(conf["rms_norm_eps"]))


def moe_mlp(conf: dict, p, y):
    """The sparse block alone on ``y [T, D]`` (parameters ``p`` as
    ``MoEMLP`` holds them): ``out [T, D]``."""
    with jax.default_matmul_precision("highest"):
        return _moe(_f32(y), p, top_k=conf["num_experts_per_tok"],
                    norm_topk=bool(conf["norm_topk_prob"]))[0]
