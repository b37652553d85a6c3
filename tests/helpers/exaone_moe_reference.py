"""K-EXAONE's block (``model_type: exaone_moe``) as a plain reference.

The forward pass in straight ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: no kernels, no cache, no
batching, no ring, no sort.  ``benchmarks/archs/exaone_moe.py`` carries
the benchmark's copy; ``tests/test_exaone_moe.py`` holds the two equal.

Layer l has ``layer_types[l]`` and ``mlp_layer_types[l]``:

- residuals ``h = x + Attn(RMSNorm(x))``, ``x' = h + MLP(RMSNorm(h))``;
  after the last layer RMSNorm and the (untied) head;
- attention: q as [H, Dh], k and v as [Hk, Dh] (Dh is ``head_dim``, not
  hidden / heads); q and k each RMS-normalised over Dh with one learned
  scale for all heads; query head h reads KV head ``h // (H // Hk)``;
  scores ``q k^T / sqrt(Dh)``, float32 softmax.  ``sliding_attention``
  rotates q and k (RoPE, ``rope_theta``) at absolute positions and
  position i sees j with ``j <= i and i - j < sliding_window`` (itself
  included); ``full_attention`` does not rotate and sees every j <= i;
- ``dense`` MLP: ``(silu(y Wg) * (y Wu)) Wd``, width ``intermediate_size``;
- ``sparse`` MLP: ``s = sigmoid(y Wr)`` over all ``router_experts``;
  the ``num_experts_per_tok`` largest of ``s + b`` are chosen (the bias
  chooses, the score weighs; ``n_group = topk_group = 1``: no group
  limit); gates ``routed_scaling_factor * s_e / sum of the chosen s``
  (``norm_topk_prob``); output ``sum_e g_e Expert_e(y) + Shared(y)``,
  each a gated-SiLU MLP of width ``moe_intermediate_size``.

``held = (lo, hi)``: the expert matrices in the parameter tree are
those of experts ``lo .. hi - 1`` (one device's share of expert
parallelism).  The router still scores every expert and every gate is
normalised over all the chosen; pairs that land outside the share add
nothing here, and the layer's output is the share's partial sum plus
the shared expert.  ``None`` = ``(0, conf["num_experts"])``: the file's
``num_experts`` is what the device holds, ``router_experts`` what the
router scores (equal in an uncut model).

``nudge``: in the compute type the model states (bfloat16) a token
whose 8th and 9th ``s + b`` nearly tie may choose the other of the two,
and is not wrong for it; this float32 pass then answers for ONE of two
honest routings.  A caller who has to judge such a token asks for the
other: ``nudge`` {layer: [B, L, E]} is added to ``s + b`` of the layers
it names before the choice (and to nothing that weighs), so +1 on one
expert and -1 on another of one token swaps the two there and leaves
every other choice, and all the arithmetic, as it was.

Departures from the published code, as ``olmoe_reference.py`` has them:
RoPE rotates interleaved pairs (x[2i], x[2i+1]) where the published
code rotates half-split pairs, and q, k, v come from one fused
``attn_qkv`` matrix: both a fixed permutation of random weights.  What
``config.json`` has no key for (pre-norm residuals, QK-norm per head,
no rotation on global layers, the selection bias) follows the family's
published description; the configuration file lists them as
``assumed``.

Weights are cast to float32 a piece at a time (one expert, one
``DENSE_SLICE`` columns of the dense MLP): at published widths the
weights are 12 GB of bfloat16 on a 16 GB chip and no layer fits beside
them in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

DENSE_SLICE = 2048


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


def _gated(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ _f32(w_gate)) * (y @ _f32(w_in))) @ _f32(w_out)


def _theta(conf: dict) -> float:
    return float((conf.get("rope_parameters") or conf)["rope_theta"])


def _router_width(conf: dict) -> int:
    return conf.get("router_experts", conf["num_experts"])


def route(y, p, conf, nudge=None):
    """``(weight [T, E], chosen [T, k])``: every token's gates as a
    dense matrix over ALL the router's experts (zero where the token
    did not choose the expert), and the experts it chose.  ``nudge``
    [T, E] is added to what CHOOSES (``s + b``), never to what weighs:
    how a caller has a near-tie between two experts resolved the other
    way for one token (module docstring)."""
    scores = jax.nn.sigmoid(y @ _f32(p["gate"]))              # [T, E]
    pick = scores + _f32(p["gate_bias"])
    if nudge is not None:
        pick = pick + nudge
    _, chosen = jax.lax.top_k(pick, conf["num_experts_per_tok"])
    vals = jnp.take_along_axis(scores, chosen, axis=-1)
    if conf["norm_topk_prob"]:
        vals = vals / vals.sum(-1, keepdims=True)
    vals = vals * float(conf["routed_scaling_factor"])
    weight = jnp.zeros_like(scores).at[
        jnp.arange(y.shape[0])[:, None], chosen].set(vals)
    return weight, chosen


def moe_mlp(conf: dict, p, y, held=None, nudge=None):
    """The sparse block on ``y [T, D]`` with the experts ``held`` (module
    docstring).  Returns ``(out [T, D], chosen [T, k])``."""
    lo, hi = held or (0, conf["num_experts"])
    weight, chosen = route(y, p, conf, nudge)

    def expert(acc, e):
        w_gate, w_in, w_out, w = e
        return acc + _gated(y, w_gate, w_in, w_out) * w[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          (p["w_gate"], p["w_in"], p["w_out"],
                           weight[:, lo:hi].T))
    shared = _gated(y, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                    p["shared_out"]["kernel"])
    return out + shared, chosen


def dense_mlp(p, y):
    """The dense block on ``y [T, D]``, ``DENSE_SLICE`` of its width at
    a time (the hidden activation is elementwise, so the slices of the
    down projection add up)."""
    wg, wu, wd = (p[n]["kernel"] for n in ("mlp_gate", "mlp_in", "mlp_out"))
    width = wg.shape[1]
    n = width // DENSE_SLICE if width % DENSE_SLICE == 0 else 1
    step = width // n

    def piece(acc, i):
        g = jax.lax.dynamic_slice_in_dim(wg, i * step, step, 1)
        u = jax.lax.dynamic_slice_in_dim(wu, i * step, step, 1)
        d = jax.lax.dynamic_slice_in_dim(wd, i * step, step, 0)
        return acc + _gated(y, g, u, d), None

    out, _ = jax.lax.scan(piece, jnp.zeros_like(y), jnp.arange(n))
    return out


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "window"))
def _attention(x, p, *, heads, kv_heads, head_dim, theta, eps, window):
    """``x + Attn(RMSNorm(x))``; ``window`` 0 = a full_attention layer
    (no rotation, every j <= i)."""
    with jax.default_matmul_precision("highest"):
        b, l, _ = x.shape
        dh = head_dim
        y = _rmsnorm(x, p["attn_norm"]["scale"], eps)
        qkv = y @ _f32(p["attn_qkv"]["kernel"])
        q, k, v = jnp.split(qkv, [heads * dh, (heads + kv_heads) * dh], -1)
        q = _rmsnorm(q.reshape(b, l, heads, dh), p["q_norm"]["scale"], eps)
        k = _rmsnorm(k.reshape(b, l, kv_heads, dh), p["k_norm"]["scale"], eps)
        v = v.reshape(b, l, kv_heads, dh)
        if window:
            q, k = _rope(q, theta), _rope(k, theta)
        g = heads // kv_heads
        k = jnp.repeat(k, g, axis=2)       # q head h reads kv head h // g
        v = jnp.repeat(v, g, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
        i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
        seen = j <= i
        if window:
            seen &= i - j < window
        s = jnp.where(seen, s, -jnp.inf)
        a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return x + a.reshape(b, l, heads * dh) @ _f32(p["attn_out"]["kernel"])


@functools.partial(jax.jit, static_argnames=("conf", "sparse", "held"))
def _mlp(x, p, nudge=None, *, conf, sparse: bool, held):
    """``x + MLP(RMSNorm(x))``; ``conf`` as ``_frozen`` gives it;
    ``nudge`` [B, L, E] or None (``route``)."""
    conf = dict(conf)
    with jax.default_matmul_precision("highest"):
        b, l, d = x.shape
        y = _rmsnorm(x, p["mlp_norm"]["scale"], float(conf["rms_norm_eps"]))
        flat = y.reshape(b * l, d)
        if sparse:
            out, chosen = moe_mlp(
                conf, p["moe"], flat, held,
                None if nudge is None else nudge.reshape(b * l, -1))
            chosen = chosen.reshape(b, l, -1)
        else:
            out, chosen = dense_mlp(p, flat), None
        out = out.reshape(b, l, d)
        return x + out, chosen, y, out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_scale, w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_scale, eps) @ _f32(w)


def _layers(params, n):
    return [params[f"layer_{i}"] for i in range(n)]


def _frozen(conf: dict):
    """The configuration as a hashable static argument."""
    keep = ("num_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "rms_norm_eps", "router_experts")
    return tuple((k, conf[k]) for k in keep if k in conf)


def forward(conf: dict, params, ids, held=None, nudge=None):
    """``(hidden [B, L, D] before the last norm, chosen {layer: [B, L,
    k]} of the sparse layers, experts {layer: (input, output) [B, L,
    D]})``: the final hidden states, every sparse layer's choice over
    ALL the router's experts, and what went into and came out of every
    sparse layer (with ``held``: this share's partial sum plus the
    shared expert).  ``nudge`` {layer: [B, L, E]} as ``route`` takes
    it, for the sparse layers it names."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    small = _frozen(conf)
    routes, experts = {}, {}
    n = conf["num_hidden_layers"]
    for i, p in enumerate(_layers(params, n)):
        window = (conf["sliding_window"]
                  if conf["layer_types"][i] == "sliding_attention" else 0)
        x = _attention(
            x, p, heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"], head_dim=conf["head_dim"],
            theta=_theta(conf), eps=float(conf["rms_norm_eps"]),
            window=window)
        sparse = conf["mlp_layer_types"][i] == "sparse"
        x, chosen, y, out = _mlp(x, p, (nudge or {}).get(i), conf=small,
                                 sparse=sparse, held=held)
        if sparse:
            routes[i], experts[i] = chosen, (y, out)
    return x, routes, experts


def reference(conf: dict, params, ids, held=None, nudge=None) -> dict:
    """The full forward pass: ``logits`` [B, L, V] float32, ``chosen``
    and ``experts`` (``forward``)."""
    x, chosen, experts = forward(conf, params, ids, held, nudge)
    return {"logits": _head(x, params["final_norm"]["scale"],
                            params["lm_head"]["kernel"],
                            eps=float(conf["rms_norm_eps"])),
            "chosen": chosen, "experts": experts}


def logits(conf: dict, params, ids, held=None):
    """[B, L, V] float32 logits of the full forward pass."""
    return reference(conf, params, ids, held)["logits"]
