"""granite-4.0-h-small's block (``model_type: granitemoehybrid``) as a
plain reference.

The forward pass in straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: the recurrence as a
``lax.scan`` over single tokens, no chunking, no kernels, no cache, no
batching, no sort.  ``benchmarks/archs/granite_moe_hybrid.py`` carries
the benchmark's copy; ``tests/test_granite_moe_hybrid.py`` holds the two
equal.

With D ``hidden_size`` and ``layer_types[l]`` ``"mamba"`` or
``"attention"``:

- embedding ``x = embedding_multiplier * E[ids]``;
- every layer ``h = x + residual_multiplier * Mixer_l(RMSNorm(x))``,
  ``x' = h + residual_multiplier * (MoE(RMSNorm(h)) + Shared(RMSNorm(h)))``
  (both MLP parts read the same normed input); after the last layer
  RMSNorm, then ``logits = (x E^T) / logits_scaling`` (tied);
- attention mixer: q as [H, Dh], k and v as [Hk, Dh], Dh = D / H; no
  bias; NO positional embedding (``position_embedding_type: "nope"``);
  query head h reads KV head ``h // (H // Hk)``; scores ``q k^T *
  attention_multiplier`` (not 1 / sqrt(Dh)), causal, float32 softmax;
- Mamba-2 mixer (H' ``mamba_n_heads`` heads of P ``mamba_d_head``, state N
  ``mamba_d_state``, G ``mamba_n_groups``, convolution ``mamba_d_conv``),
  with y the normed input: ``[z | xBC | dt] = y W_in`` (widths H'P | H'P +
  2GN | H'); ``xBC_t = silu(b_c + sum_i w_c[i] * xBC_{t-(K-1)+i})``
  (depthwise, causal, zeros before the start); ``[x | B | C] = xBC``;
  ``dt_t = softplus(dt_t + dt_bias)``, ``a_t = exp(dt_t * A)``, ``A =
  -exp(A_log)`` per head; ``S_t = a_t S_{t-1} + dt_t x_t (outer) B_t``,
  ``S_{-1} = 0``; ``o_t = S_t C_t + D * x_t``; ``u = RMSNorm(o *
  silu(z))`` over the whole inner width with a learned scale (gate
  first, then norm; one group); output ``u W_out``;
- MoE: ``logits = y W_r`` over all ``router_experts``; T = the
  ``num_experts_per_tok`` largest; ``g = softmax(logits[T])``; output
  ``sum_{e in T} g_e Expert_e(y)``, ``Expert_e(y) = (silu(y Wg_e) * (y
  Wu_e)) Wd_e`` of width ``intermediate_size``; ``Shared`` the same form
  at ``shared_intermediate_size``, every token.  Nothing is dropped.

``held = (lo, hi)``: the expert matrices in the parameter tree are
those of experts ``lo .. hi - 1`` (one device's share of expert
parallelism).  The router still scores every expert and the gates are
normalised over all the chosen; pairs that land outside the share add
nothing here, and the layer's output is the share's partial sum plus
the shared MLP.  ``None`` = ``(0, conf["num_local_experts"])``: the
file's ``num_local_experts`` is what the device holds,
``router_experts`` what the router scores (equal in an uncut model).

Departures from the published code: ``W_in``, the fused ``attn_qkv`` and
the separate gate / up matrices are fixed permutations of random
weights; ``time_step_limit`` is (0, inf), so dt is not clamped.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _gated(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ _f32(w_gate)) * (y @ _f32(w_in))) @ _f32(w_out)


def _router_width(conf: dict) -> int:
    return conf.get("router_experts", conf["num_local_experts"])


def route(y, p, conf):
    """``(weight [T, E], chosen [T, k])``: every token's gates as a
    dense matrix over ALL the router's experts, and the experts it
    chose: the k largest logits, softmax over those k."""
    logits = y @ _f32(p["gate"])                               # [T, E]
    vals, chosen = jax.lax.top_k(logits, conf["num_experts_per_tok"])
    vals = jax.nn.softmax(vals, axis=-1)
    weight = jnp.zeros_like(logits).at[
        jnp.arange(y.shape[0])[:, None], chosen].set(vals)
    return weight, chosen


def held_experts(conf: dict, p, y, held=None):
    """The experts ``held`` (module docstring) ALONE on ``y [T, D]``:
    this share's partial sum, the shared MLP not in it.  ``(out [T, D],
    chosen)``."""
    lo, hi = held or (0, conf["num_local_experts"])
    weight, chosen = route(y, p, conf)

    def expert(acc, e):
        w_gate, w_in, w_out, w = e
        return acc + _gated(y, w_gate, w_in, w_out) * w[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          (p["w_gate"], p["w_in"], p["w_out"],
                           weight[:, lo:hi].T))
    return out, chosen


def moe_mlp(conf: dict, p, y, held=None):
    """The expert block on ``y [T, D]``: ``held_experts`` and the shared
    MLP.  ``(out [T, D], chosen, the held experts' partial sum)``."""
    routed, chosen = held_experts(conf, p, y, held)
    shared = _gated(y, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                    p["shared_out"]["kernel"])
    return routed + shared, chosen, routed


def mamba_mixer(conf: dict, p, y):
    """The Mamba-2 mixer on ``y [B, L, D]`` (normed input): the plain
    recurrence, one token at a time, from a zero state.  ``(out [B, L,
    D], the state after the last token [B, H, P, N])``."""
    H, P, N = conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"]
    G, K = conf["mamba_n_groups"], conf["mamba_d_conv"]
    di = H * P
    b, l, _ = y.shape
    zxbcdt = y @ _f32(p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    w = _f32(p["conv_w"])                                      # [K, Cd]
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = sum(padded[:, i:i + l] * w[i] for i in range(K))
    if "conv_b" in p:
        xbc = xbc + _f32(p["conv_b"])
    xbc = jax.nn.silu(xbc)
    x, bm, cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    x = x.reshape(b, l, H, P)
    bm = jnp.repeat(bm.reshape(b, l, G, N), H // G, axis=2)
    cm = jnp.repeat(cm.reshape(b, l, G, N), H // G, axis=2)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))              # [B, L, H]
    a = jnp.exp(dt * -jnp.exp(_f32(p["A_log"])))

    def step(s, t):
        xt, bt, ct, dtt, at = t
        s = (s * at[..., None, None]
             + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct)

    last, o = jax.lax.scan(
        step, jnp.zeros((b, H, P, N), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, bm, cm, dt, a)))
    o = jnp.moveaxis(o, 0, 1) + _f32(p["D"])[:, None] * x
    g = o.reshape(b, l, di) * jax.nn.silu(z)
    u = _rmsnorm(g, p["norm"]["scale"], float(conf["rms_norm_eps"]))
    return u @ _f32(p["out_proj"]["kernel"]), last


def attention_mixer(conf: dict, p, y):
    """The attention mixer on ``y [B, L, D]``: no rotation, scores times
    ``attention_multiplier``."""
    heads, kv_heads = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = conf["hidden_size"] // heads
    b, l, _ = y.shape
    qkv = y @ _f32(p["attn_qkv"]["kernel"])
    q, k, v = jnp.split(qkv, [heads * dh, (heads + kv_heads) * dh], -1)
    q = q.reshape(b, l, heads, dh)
    g = heads // kv_heads
    k = jnp.repeat(k.reshape(b, l, kv_heads, dh), g, axis=2)
    v = jnp.repeat(v.reshape(b, l, kv_heads, dh), g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * float(
        conf["attention_multiplier"])
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    s = jnp.where(j <= i, s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    return a.reshape(b, l, heads * dh) @ _f32(p["attn_out"]["kernel"])


_MIXER_KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
               "mamba_n_groups", "mamba_d_conv", "rms_norm_eps",
               "num_attention_heads", "num_key_value_heads", "hidden_size",
               "attention_multiplier", "residual_multiplier")
_MLP_KEYS = ("num_local_experts", "router_experts", "num_experts_per_tok",
             "rms_norm_eps", "residual_multiplier")


def _frozen(conf: dict, keys):
    """The configuration as a hashable static argument."""
    return tuple((k, conf[k]) for k in keys if k in conf)


@functools.partial(jax.jit, static_argnames=("conf", "kind"))
def _mixer(x, p, *, conf, kind):
    """``(x + m * Mixer(RMSNorm(x)), the normed input, the mixer's
    output, a Mamba-2 mixer's state after the last token or None)``."""
    conf = dict(conf)
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(x, p["attn_norm"]["scale"], float(conf["rms_norm_eps"]))
        out, state = (mamba_mixer(conf, p["ssm"], y) if kind == "mamba"
                      else (attention_mixer(conf, p, y), None))
        return x + float(conf["residual_multiplier"]) * out, y, out, state


@functools.partial(jax.jit, static_argnames=("conf", "held"))
def _mlp(x, p, *, conf, held):
    conf = dict(conf)
    with jax.default_matmul_precision("highest"):
        b, l, d = x.shape
        y = _rmsnorm(x, p["mlp_norm"]["scale"], float(conf["rms_norm_eps"]))
        out, chosen, routed = moe_mlp(conf, p["moe"], y.reshape(b * l, d),
                                      held)
        out = out.reshape(b, l, d)
        return (x + float(conf["residual_multiplier"]) * out,
                chosen.reshape(b, l, -1), y, out, routed.reshape(b, l, d))


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def _head(x, norm_scale, embedding, *, eps, scaling):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_scale, eps) @ _f32(embedding).T / scaling


def forward(conf: dict, params, ids, held=None):
    """``(hidden [B, L, D] before the last norm, chosen {layer: [B, L,
    k]}, experts {layer: (input, output, the held experts' part of the
    output)}, mixers {layer: (input, output, the layer's input before
    its norm, a Mamba-2 mixer's final state)})``: every layer's choice
    over ALL the router's experts, what went into and came out of every
    expert block (with ``held``: this share's partial sum plus the
    shared MLP) and every mixer."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0)) * float(
        conf["embedding_multiplier"])
    routes, experts, mixers = {}, {}, {}
    for i in range(conf["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        before = x
        x, y, out, state = _mixer(x, p, conf=_frozen(conf, _MIXER_KEYS),
                                  kind=conf["layer_types"][i])
        mixers[i] = (y, out, before, state)
        x, chosen, y, out, routed = _mlp(
            x, p, conf=_frozen(conf, _MLP_KEYS), held=held)
        routes[i], experts[i] = chosen, (y, out, routed)
    return x, routes, experts, mixers


def reference(conf: dict, params, ids, held=None) -> dict:
    """The full forward pass: ``logits`` [B, L, V] float32, ``chosen``,
    ``experts`` and ``mixers`` (``forward``)."""
    x, chosen, experts, mixers = forward(conf, params, ids, held)
    return {"logits": _head(x, params["final_norm"]["scale"],
                            params["tok_embed"]["embedding"],
                            eps=float(conf["rms_norm_eps"]),
                            scaling=float(conf["logits_scaling"])),
            "chosen": chosen, "experts": experts, "mixers": mixers}


def logits(conf: dict, params, ids, held=None):
    """[B, L, V] float32 logits of the full forward pass."""
    return reference(conf, params, ids, held)["logits"]
