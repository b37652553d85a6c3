"""K-EXAONE-236B-A23B (``exaone_moe``) for the benchmark: configuration,
weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module, as
``archs/olmoe.py`` is: ``runners/serve_hybrid.py`` registers it as
``model`` and its ``reference`` as ``reference``, and
``runners/serve.py`` then calls ``transformer_config``, ``init_params``
and ``logits`` exactly as it calls ``model.py`` and ``reference.py``.
``block_agreement`` is what the cell's ``correct`` also rests on: the
program's own block, expert layer by expert layer and as a whole,
against the reference on the probe, and the host's recount of the
(token, expert) pairs that landed on the held experts.

The reference is a copy of ``tests/helpers/exaone_moe_reference.py``
(a tier-1 test holds the two equal) and follows these equations.  Layer
l has ``layer_types[l]`` and ``mlp_layer_types[l]``:

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
router scores.

``nudge``: in the compute type the model states (bfloat16) a token
whose 8th and 9th ``s + b`` nearly tie may choose the other of the two,
and is not wrong for it; this float32 pass then answers for ONE of two
honest routings.  A caller who has to judge such a token asks for the
other: ``nudge`` {layer: [B, L, E]} is added to ``s + b`` of the layers
it names before the choice (and to nothing that weighs), so +1 on one
expert and -1 on another of one token swaps the two there and leaves
every other choice, and all the arithmetic, as it was.

Departures from the published code, as ``archs/olmoe.py`` has them:
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

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

# every key of the published config.json the catalog keeps, and the
# benchmark's own; a key outside both is refused, not ignored
PUBLISHED = {"first_k_dense_replace", "head_dim", "hidden_act", "hidden_size",
             "intermediate_size", "layer_types", "max_position_embeddings",
             "mlp_layer_types", "model_type", "moe_intermediate_size",
             "mtp_layer_types", "mtp_sliding_windows", "n_group",
             "norm_topk_prob", "num_attention_heads", "num_experts",
             "num_experts_per_tok", "num_hidden_layers",
             "num_key_value_heads", "num_nextn_predict_layers",
             "num_shared_experts", "rms_norm_eps", "rope_parameters",
             "routed_scaling_factor", "scoring_func", "sliding_window",
             "sliding_window_pattern", "sliding_windows",
             "tie_word_embeddings", "topk_group", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "deployment", "run", "memory", "sizing_notes",
       "router_experts"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/exaone_moe.py maps no key {unknown}: a key "
                         f"it ignored would run another model under this "
                         f"name")
    want = {"model_type": "exaone_moe", "hidden_act": "silu",
            "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
            "num_shared_experts": 1, "tie_word_embeddings": False,
            "num_nextn_predict_layers": 0, "first_k_dense_replace": 1}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")
    if conf["rope_parameters"].get("rope_type", "default") != "default":
        raise ValueError("rope_parameters: the default rotation only")
    n = conf["num_hidden_layers"]
    for name, kinds in (("layer_types", {"sliding_attention",
                                         "full_attention"}),
                        ("mlp_layer_types", {"dense", "sparse"})):
        if len(conf[name]) < n or set(conf[name]) - kinds:
            raise ValueError(f"{name} must name one of {sorted(kinds)} for "
                             f"each of the {n} layers")
    for kind, size in zip(conf["layer_types"][:n], conf["sliding_windows"]):
        if size != (conf["sliding_window"] if kind == "sliding_attention"
                    else 0):
            raise ValueError("sliding_windows disagrees with layer_types")
    dense = [m == "dense" for m in conf["mlp_layer_types"][:n]]
    if dense != [i < conf["first_k_dense_replace"] for i in range(n)]:
        raise ValueError("mlp_layer_types disagrees with "
                         "first_k_dense_replace")
    if not 0 < conf["num_experts"] <= _router_width(conf):
        raise ValueError("num_experts (held here) exceeds router_experts")


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    n = conf["num_hidden_layers"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["run"]["compute_dtype"]]
    router = _router_width(conf)
    kw = dict(vocab_size=conf["vocab_size"], num_layers=n,
              embed_dim=conf["hidden_size"],
              num_heads=conf["num_attention_heads"],
              num_kv_heads=conf["num_key_value_heads"],
              attn_head_dim=conf["head_dim"],
              mlp_dim=conf["intermediate_size"],
              moe_mlp_dim=conf["moe_intermediate_size"], max_len=max_len,
              rope_theta=_theta(conf), tie_embeddings=False, dtype=dtype,
              attention_impl=conf["run"].get("attention", "auto"),
              norm_eps=float(conf["rms_norm_eps"]), qk_norm=True,
              qk_norm_per_head=True, attn_window=conf["sliding_window"],
              layer_attn=tuple(
                  "window" if t == "sliding_attention" else "global"
                  for t in conf["layer_types"][:n]),
              layer_mlp=tuple(conf["mlp_layer_types"][:n]),
              rope_global=False, moe_experts=router,
              moe_held=(conf["num_experts"]
                        if conf["num_experts"] < router else 0),
              moe_top_k=conf["num_experts_per_tok"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=bool(conf["norm_topk_prob"]),
              moe_router="sigmoid", moe_select_bias=True,
              moe_routed_scale=float(conf["routed_scaling_factor"]),
              moe_shared_dim=(conf["num_shared_experts"]
                              * conf["moe_intermediate_size"]))
    kw.update(overrides)
    return TransformerConfig(**kw)


BIAS_SCALE = 0.05


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device from the seed, one layer per
    jitted call and cast inside it (a sparse layer made in float32 and
    cast afterwards would need 3 GB for a moment, beside 10 GB of its
    predecessors).  Always ``layer_<i>``: layers that differ cannot be
    stacked.

    The layers' matrices are the program's own initialisers, with the
    corrections ``archs/olmoe.py`` found necessary (PERF.md section 6,
    PR 26), without which the comparison with the reference is blind
    to the expert layers: each expert matrix lecun-normal BY ITSELF
    (``MoEMLP``'s initialiser counts the expert axis as a receptive
    field, so its matrices come out sqrt(held) too small), embedding
    rows unit normal, norm scales 1 + 0.1 * normal so that a misplaced
    scale shows.  The selection bias is ``BIAS_SCALE`` * normal: small
    beside the scores' spread (a sigmoid of a unit-normal logit), not
    zero, so that a router that leaves it out or weighs with it picks
    and weighs differently."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block

    if not split_layers:
        raise ValueError("a stack whose layers differ has no stacked layout")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]
    D, V = cfg.embed_dim, cfg.vocab_size

    def cast(path, a, key):
        if path[-1].key == "scale":
            a = 1.0 + 0.1 * jax.random.normal(key, a.shape, jnp.float32)
        elif path[-1].key == "gate_bias":
            a = BIAS_SCALE * jax.random.normal(key, a.shape, jnp.float32)
        elif a.ndim == 3:                       # [experts, in, out]
            a = a * a.shape[0] ** 0.5
        return a.astype(dt)

    def scaled(tree, key):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [cast(p, a, k) for (p, a), k in zip(leaves, keys)])

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(key, i):
        k1, k2 = jax.random.split(key)
        p = Block(cfg, i).init(k1, jnp.zeros((1, 8, D), cfg.dtype),
                               jnp.zeros((1, 8), jnp.int32))["params"]
        return scaled(p, k2)

    @jax.jit
    def ends(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return scaled(
            {"tok_embed": {"embedding": jax.random.normal(k1, (V, D))},
             "final_norm": {"scale": jnp.ones((D,))},
             "lm_head": {"kernel":
                         nn.initializers.lecun_normal()(k2, (D, V))}}, k3)

    keys = jax.random.split(jax.random.key(seed % (1 << 31)),
                            cfg.num_layers + 1)
    params = ends(keys[0])
    for i, k in enumerate(keys[1:]):
        params[f"layer_{i}"] = layer(k, i)
    return params


# -- the reference (tests/helpers/exaone_moe_reference.py, copied) -----------
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


# -- the program's block, for the comparison ---------------------------------
def program_forward(cfg, params, ids):
    """The PROGRAM's block over ``ids``: ``edl_tpu``'s ``Block`` layer
    by layer, its final norm and head, in ``cfg``'s compute type (full
    forward, dense attention under each layer's own mask, no cache).
    Returns ``(logits [B, L, V] float32, chosen {layer: [B, L, k]})``,
    the experts each sparse layer's float32 router picked from the
    block's own ``mlp_norm`` output."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block, RMSNorm
    from edl_tpu.ops.moe import sigmoid_gates

    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = jnp.take(params["tok_embed"]["embedding"], ids, axis=0).astype(
        cfg.dtype)
    routes = {}
    for i, p in enumerate(_layers(params, cfg.num_layers)):
        (x, _), seen = Block(cfg, i).apply(
            {"params": p}, x, pos, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "mlp_norm")
        if cfg.mlp_kind(i) != "sparse":
            continue
        y = seen["intermediates"]["mlp_norm"]["__call__"][0]
        scores = jax.nn.sigmoid(_f32(y) @ _f32(p["moe"]["gate"]))
        routes[i] = sigmoid_gates(
            scores, _f32(p["moe"]["gate_bias"]) if cfg.moe_select_bias
            else None, cfg.moe_top_k, cfg.moe_norm_topk)[1]
    x = RMSNorm(cfg.dtype, cfg.norm_eps).apply(
        {"params": params["final_norm"]}, x)
    out = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype).apply(
        {"params": params["lm_head"]}, x)
    return _f32(out), routes


def program_experts(cfg, moe_params, y):
    """The PROGRAM's expert layer alone (``ops/moe.py``'s ``MoEMLP`` as
    ``Block`` builds it: router, held experts, shared expert) on ``y``
    [B, L, D], in ``cfg``'s compute type."""
    from edl_tpu.ops.moe import MoEMLP

    layer = MoEMLP(num_experts=cfg.moe_experts, mlp_dim=cfg.expert_dim,
                   top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity,
                   dtype=cfg.dtype, gated=cfg.moe_gated,
                   norm_topk=cfg.moe_norm_topk, router=cfg.moe_router,
                   select_bias=cfg.moe_select_bias,
                   routed_scale=cfg.moe_routed_scale,
                   shared_dim=cfg.moe_shared_dim, held=cfg.moe_held)
    (out, _), _ = layer.apply({"params": moe_params}, y.astype(cfg.dtype),
                              mutable=["intermediates"])
    return _f32(out)


def shared_reference(moe_params, y):
    """The reference's shared expert on ``y`` [B, L, D], float32."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(_gated)(
            y, *(moe_params[n]["kernel"]
                 for n in ("shared_gate", "shared_in", "shared_out")))


def held_pairs(conf: dict, chosen: dict, upto: int | None = None) -> int:
    """The host's recount: of the reference router's (token, expert)
    pairs over the first ``upto`` positions, those that land on the
    experts held here, summed over the sparse layers."""
    import numpy as np
    return int(sum((np.asarray(c)[:, :upto] < conf["num_experts"]).sum()
                   for c in chosen.values()))


def block_agreement(conf: dict, params, ids, ref: dict, *, cfg=None,
                    program_params=None, tag: str = "") -> dict:
    """The program's block (``cfg`` and ``program_params`` let a
    deliberately wrong variant stand in) against ``reference``'s ``ref``
    on the same ``ids``, as ``archs/olmoe.py`` compares, and prints:

    ``expert_error`` [sparse layers * B * L]: every expert layer ALONE
    (router, held experts, shared expert), fed the reference's own
    input to that layer: the norm of (program - reference) over the
    norm of the reference's output, a token.  The median over tokens
    and layers, because a token whose 8th and 9th expert swap on the
    rounded input is far out and honest.
    ``held_expert_error``: the same with the shared expert taken out of
    both sides, over the tokens that chose at least one held expert:
    the routed experts' partial sum alone.  The shared expert is whole
    on every token and only one pair in eight lands on a held expert,
    so in ``expert_error`` the held experts' own precision is lost:
    experts rounded to int8 read 0.0057 there beside the honest 0.0049
    (my chip run 5, PR 30), and this measure is what tells them apart.
    ``logit_error_sigma`` [B * L]: the whole block (``program_forward``)
    at the level of logits: at every position the root mean square over
    the vocabulary of (program - reference), in standard deviations of
    the reference's logits there.  It sees every layer, window and
    global attention and the dense layer included.
    ``expert_sets_differ``: the share of (token, sparse layer) pairs
    whose chosen set in the whole block differs from the reference's.
    ``held_pairs``: the host's recount (``held_pairs``)."""
    import numpy as np

    cfg = cfg or transformer_config(conf, max_len=ids.shape[1], remat=False,
                                    attention_impl="dense")
    program_params = params if program_params is None else program_params
    own, picked = program_forward(cfg, program_params, ids)
    want = ref["logits"]
    differ = float(np.mean([
        np.asarray((jnp.sort(picked[i], -1) != jnp.sort(c, -1)).any(-1))
        for i, c in ref["chosen"].items() if i in picked] or [0.0]))
    err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(own - want), -1))
                     / jnp.std(want, -1)).reshape(-1)
    alone, routed = [], []
    bare = dataclasses.replace(cfg, moe_shared_dim=0)
    for i, (y, out) in ref["experts"].items():
        moe = program_params[f"layer_{i}"]["moe"]
        diff = program_experts(cfg, moe, y) - out
        alone.append(np.asarray(jnp.linalg.norm(diff, axis=-1)
                                / jnp.linalg.norm(out, axis=-1)).reshape(-1))
        # the held experts' partial sum alone, where a token has one
        want_routed = out - shared_reference(params[f"layer_{i}"]["moe"], y)
        diff = program_experts(bare, moe, y) - want_routed
        on = np.asarray((ref["chosen"][i] < conf["num_experts"]).any(-1))
        routed.append(np.asarray(
            jnp.linalg.norm(diff, axis=-1)
            / jnp.maximum(jnp.linalg.norm(want_routed, axis=-1), 1e-30))[on])
    alone, routed = np.concatenate(alone), np.concatenate(routed)
    pairs = held_pairs(conf, ref["chosen"])
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: expert layers alone, error over norm, median "
          f"{np.median(alone):.5f} mean {alone.mean():.5f} over {alone.size} "
          f"(token, layer) pairs, the held experts' partial sum alone median "
          f"{np.median(routed):.5f} over {routed.size}; logits, median "
          f"{np.median(err):.5f} mean "
          f"{err.mean():.5f} max {err.max():.5f} sigma over {err.size} "
          f"positions; expert sets differ in {100 * differ:.3f}% of the "
          f"(token, layer) pairs; {pairs} pairs on held experts", flush=True)
    return {"expert_error": alone, "held_expert_error": routed,
            "logit_error_sigma": err, "expert_sets_differ": differ,
            "held_pairs": pairs}


# -- a served token where the router nearly tied -----------------------------
@jax.jit
def _selection_scores(gate, bias, y):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(y @ _f32(gate)) + _f32(bias)


def held_swaps(v, held: int, top_k: int, delta: float) -> list:
    """``[(gap, out, in)]``, nearest tie first: the swaps of one chosen
    expert for one unchosen one that change WHICH HELD EXPERTS one token
    computes, among the pairs whose selection scores ``v`` [E] lie
    within ``delta`` of each other: a held expert that leaves for the
    best of the unchosen, or a held expert that enters for the weakest
    of the chosen.  A swap between two experts held elsewhere moves
    nothing on this device but the gates' sum."""
    import numpy as np
    order = np.argsort(-v, kind="stable")
    chosen, rest = order[:top_k], order[top_k:]
    weakest, best = int(chosen[-1]), int(rest[0])
    swaps = {(float(v[e] - v[best]), int(e), best)
             for e in chosen if e < held and v[e] - v[best] < delta}
    swaps |= {(float(v[weakest] - v[e]), weakest, int(e))
              for e in rest if e < held and v[weakest] - v[e] < delta}
    return sorted(swaps)


def tie_aware_shortfall(conf: dict, params, ids, ref: dict, at: int,
                        token: int, *, limit: float, delta: float,
                        depth: int = 2, passes: int = 24) -> dict:
    """How far the reference's logit of ``token`` at position ``at``
    lies under its best there, in standard deviations of that row, under
    the HONEST ROUTING NEAREST TO THE TOKEN: ``ref`` itself (``plain``),
    or the reference with up to ``depth`` of position ``at``'s own
    near-ties (``held_swaps`` within ``delta``) resolved the other way,
    one sparse layer each, the layers after a swap routed by the
    reference itself on what the swap left them.  The search runs only
    where ``plain`` is over ``limit``, stops at the first routing under
    which the token is within ``limit``, and spends at most ``passes``
    reference passes.  Only the column of ``at`` is touched: of a
    position's error at the level of logits, its own held experts'
    entering and leaving explains nine tenths (PERF.md section 6,
    PR 30); what an earlier position's swap sends it through attention
    is within the rounding.

    Returns ``{"plain", "shortfall", "swaps" [(layer, out, in, gap)],
    "passes"}``."""
    import numpy as np

    def column(r):
        # all the search keeps of a pass: position ``at``'s logits and
        # what went into each of its sparse layers
        return (np.asarray(r["logits"][0, at]),
                {i: y[0, at] for i, (y, _) in r["experts"].items()})

    def short(row):
        return float((row.max() - row[token]) / row.std())

    held, k = conf["num_experts"], conf["num_experts_per_tok"]
    width = _router_width(conf)
    row, into_layers = column(ref)
    found = {"plain": short(row), "shortfall": short(row), "swaps": [],
             "passes": 0}
    if found["plain"] <= limit:
        return found
    level = [((), into_layers)]             # (swaps taken, that pass's inputs)
    for _ in range(depth):
        nxt = []
        for swaps, inputs in level:
            cands = []
            for i, y in inputs.items():
                if swaps and i <= swaps[-1][0]:
                    continue                # a pair of layers once, in order
                moe = params[f"layer_{i}"]["moe"]
                v = np.asarray(_selection_scores(
                    moe["gate"], moe["gate_bias"], y[None]))[0]
                cands += [(gap, i, out, into)
                          for gap, out, into in held_swaps(v, held, k, delta)]
            for gap, i, out, into in sorted(cands):
                if found["passes"] >= passes:
                    return found
                took = swaps + ((i, out, into, gap),)
                nudge = {}
                for layer, e_out, e_in, _ in took:
                    one = np.zeros((width,), np.float32)
                    one[e_out], one[e_in] = -1.0, 1.0
                    nudge[layer] = jnp.zeros(
                        ids.shape + (width,), jnp.float32).at[0, at].set(one)
                row, inputs2 = column(reference(conf, params, ids,
                                                nudge=nudge))
                found["passes"] += 1
                if short(row) < found["shortfall"]:
                    found["shortfall"], found["swaps"] = short(row), list(took)
                if found["shortfall"] <= limit:
                    return found
                nxt.append((took, inputs2))
        level = nxt
    return found


# -- what the algorithms need, from shapes alone ------------------------------
def _kinds(conf: dict):
    n = conf["num_hidden_layers"]
    return conf["layer_types"][:n], conf["mlp_layer_types"][:n]


def sparse_layers(conf: dict) -> int:
    return _kinds(conf)[1].count("sparse")


def window_layers(conf: dict) -> int:
    return _kinds(conf)[0].count("sliding_attention")


def expert_params(conf: dict) -> int:
    """One routed expert (or the shared one): gate, up and down."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def expert_flops_per_assignment(conf: dict) -> float:
    """One (token, expert) pair: three matmuls, 2 FLOPs a weight."""
    return 2.0 * expert_params(conf)


def attention_params(conf: dict) -> int:
    d, dh = conf["hidden_size"], conf["head_dim"]
    h, hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    return d * (h + 2 * hk) * dh + h * dh * d


def shared_matmul_params(conf: dict) -> int:
    """Read by every token, all layers together: attention; the dense
    layers' MLP; a sparse layer's router and shared expert."""
    d = conf["hidden_size"]
    sparse = sparse_layers(conf)
    return (conf["num_hidden_layers"] * attention_params(conf)
            + (conf["num_hidden_layers"] - sparse) * 3 * d
            * conf["intermediate_size"]
            + sparse * (d * _router_width(conf)
                        + conf["num_shared_experts"] * expert_params(conf)))


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Of the GLOBAL layers: the only cache that grows with the
    context."""
    return (2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize
            * (conf["num_hidden_layers"] - window_layers(conf)))


def param_count(conf: dict) -> int:
    """Every parameter this device holds (``num_experts`` routed
    experts a sparse layer, the router whole, the vocabulary slice)."""
    d, n = conf["hidden_size"], conf["num_hidden_layers"]
    sparse = sparse_layers(conf)
    norms = 2 * d + 2 * conf["head_dim"]     # attn, mlp; q_norm, k_norm
    return (2 * conf["vocab_size"] * d + d + shared_matmul_params(conf)
            + sparse * (conf["num_experts"] * expert_params(conf)
                        + _router_width(conf))          # selection bias
            + n * norms)


def decode_step_min_bytes(conf: dict, experts_touched: float,
                          live_tokens: float, itemsize: int = 2) -> float:
    """What one decode token step must read at least: attention, dense,
    router, shared-expert and head weights once, the held experts its
    batch touched (a sparse layer's mean) in every sparse layer, and
    the global layers' live keys and values.  The window layers' rings
    are left out: at most ``sliding_window`` positions a live slot a
    layer, 6 x 12 x 128 x 4 KiB = 37.7 MB against 3.3 GB, under 1% of a
    step (``window_attention_roofline`` has them)."""
    shared = (shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = sparse_layers(conf) * experts_touched * expert_params(conf)
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens)


def expert_matmul_min(conf: dict, assignments: float, experts_read: float,
                      itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` the grouped matmuls need for ``assignments``
    (token, expert) pairs on HELD experts (the pairs computed here) that
    made the program read ``experts_read`` expert weight sets (summed
    over layers and programs): the weights once, and each pair's input
    row read and output row written for the three projections."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    rows = assignments * (d + 2 * m + m + d) * itemsize
    return (assignments * expert_flops_per_assignment(conf),
            experts_read * expert_params(conf) * itemsize + rows)


def window_attention_min(conf: dict, positions: float,
                         itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of the window layers' one-token append and
    attend, all window layers together, for live (slot, token step)
    pairs whose windows held ``positions`` keys in all (``sum
    min(length, sliding_window)``, of ONE layer): scores and weighted
    values, 2 FLOPs a multiply-add, every query head against its
    window; the window's keys and values read once.  The step's own
    row (one key and value written, one query read, one output written)
    is 1/128 of that at a full window and left out."""
    h, hk, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    flops = 2 * 2.0 * h * dh * positions
    nbytes = 2.0 * hk * dh * positions * itemsize
    return window_layers(conf) * flops, window_layers(conf) * nbytes
