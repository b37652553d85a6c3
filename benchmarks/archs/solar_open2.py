"""Solar-Open2-250B (``solar_open2``) for the benchmark: configuration,
weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module, as
``archs/kimi_linear.py`` is: ``runners/serve_deltagqa.py`` registers it
as ``model`` and its ``reference`` as ``reference``, and
``runners/serve.py`` then calls ``transformer_config``, ``init_params``
and ``logits`` exactly as it calls ``model.py`` and ``reference.py``.
``block_agreement`` and ``long_prefix_agreement`` are what the cell's
``correct`` also rests on.

The reference is the forward pass in plain ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: the delta rule one token at
a time, the attention over every visible row, no cache, no kernels, no
chunks, no sort.  Nothing of ``edl_tpu`` is in it (the tier-1 tests in
``tests/test_solar_open2.py`` hold the program to it at a toy size).
Its equations, with D ``hidden_size``, ``y = RMSNorm(x)`` (eps
``rms_norm_eps``), every layer ``x += Mixer(RMSNorm(x))``, ``x +=
MLP(RMSNorm(x))``, a final RMSNorm and an untied head, no positional
embedding anywhere:

- layer ``i`` in ``gqa_layers`` (0, 4, 8, ...): ``q = y W_q`` ``[H,
  Dh]`` (64 x 128), ``k, v = y W_k, y W_v`` ``[Hk, Dh]`` (8 x 128), NO
  rotation (``use_rope: false``; ``rope_theta`` and
  ``partial_rotary_factor`` are carried and unused), causal softmax at
  scale ``Dh ** -0.5`` in float32, query head ``h`` on KV head ``h //
  (H // Hk)``; ``o = softmax(..) v * sigmoid(y W_gate)`` elementwise
  ``[H x Dh]`` (``use_gqa_gate``); ``x += o W_o``;
- the other three of four: the KDA mixer of ``archs/kimi_linear.py``
  (``q, k, v = SiLU(conv(W x))``, depthwise causal convolutions over
  ``short_conv_kernel_size`` positions, no bias; ``q``, ``k``
  L2-normalised a head, ``q`` times ``R ** -0.5``; ``g = -exp(A_log[h])
  * softplus(W_f2 (W_f1 x) + dt_bias)`` a key channel through the
  R-wide low-rank path, ``kda_use_full_proj: false``; state ``S [R, R]``
  a head: ``S' = Diag(exp(g)) S``, ``u = v - S'^T k``, ``S = S' + beta k
  u^T``, ``o = S^T q``; ``W_o (RMSNorm_head(o) * sigmoid(W_g2 (W_g1
  x)))``) with ``linear_attn_config.num_heads`` heads of ``head_dim``
  (``num_kv_heads: null`` = as many) and **``beta = 2 sigmoid(W_b x)``**
  (``kda_allow_neg_eigval: true``: the transition ``I - beta k k^T`` has
  an eigenvalue in [-1, 1]);
- every layer's MLP (``first_k_dense_replace: 0``; ``intermediate_size``
  is carried and unused): ``sigmoid(W_r y)`` over the router's experts,
  chosen by score + a learned bias (it chooses, the score weighs), the
  ``num_experts_per_tok`` largest, renormalised to sum 1
  (``norm_topk_prob``) times ``routed_scaling_factor``; experts
  SiLU-gated of ``moe_intermediate_size``; ``n_shared_experts`` shared
  expert on the same input.

``held = (lo, hi)``: one device's share of expert parallelism, as in
``archs/exaone_moe.py``: the router scores all ``router_experts``, the
gates are normalised over all the chosen, this device computes the pairs
that land on experts ``lo .. hi - 1`` and the shared expert.

Assumed (``config.json`` has no key; the configuration file lists them):
the gate's form (one D x H Dh matrix of the layer's normed input, a
sigmoid, no bias), no QK-norm, the router's score function (sigmoid with
a selection bias).  Departures from the published code: q, k, v, the two
low-rank inputs and beta of a KDA layer come from one fused ``in_proj``,
and q, k, v of a GQA layer from one fused ``attn_qkv`` (fixed
permutations of random weights).

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

PUBLISHED = {"model_type", "partial_rotary_factor", "linear_attn_config",
             "hidden_size", "num_hidden_layers", "num_attention_heads",
             "head_dim", "num_key_value_heads", "vocab_size",
             "intermediate_size", "moe_intermediate_size", "rms_norm_eps",
             "rope_theta", "tie_word_embeddings", "max_position_embeddings",
             "first_k_dense_replace", "use_rope", "gqa_interval",
             "gqa_layers", "use_gqa_gate", "kda_use_full_proj",
             "kda_allow_neg_eigval", "n_routed_experts", "n_shared_experts",
             "norm_topk_prob", "routed_scaling_factor",
             "num_experts_per_tok"}
OWN = {"source", "torch_dtype", "reduced", "reduced_from", "assumed",
       "deployment", "run", "memory", "sizing_notes", "router_experts"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/solar_open2.py maps no key {unknown}: a "
                         f"key it ignored would run another model under "
                         f"this name")
    want = {"model_type": "solar_open2", "first_k_dense_replace": 0,
            "kda_use_full_proj": False, "tie_word_embeddings": False}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")
    lin = conf["linear_attn_config"]
    if set(lin) != {"short_conv_kernel_size", "head_dim", "num_heads",
                    "num_kv_heads"}:
        raise ValueError(f"linear_attn_config has keys {sorted(lin)}")
    if lin["num_kv_heads"] not in (None, lin["num_heads"]):
        raise ValueError("the program's delta-rule layer has as many key "
                         "and value heads as query heads")
    if conf["num_attention_heads"] % conf["num_key_value_heads"]:
        raise ValueError("query heads in whole groups of KV heads")
    if not 0 < conf["n_routed_experts"] <= _router_width(conf):
        raise ValueError("n_routed_experts (held here) exceeds "
                         "router_experts")


def layer_kinds(conf: dict) -> list:
    """``"global"`` (a gated GQA layer) or ``"kda"`` for each of the
    first ``num_hidden_layers`` layers (``gqa_layers`` is 0-based)."""
    return ["global" if i in conf["gqa_layers"] else "kda"
            for i in range(conf["num_hidden_layers"])]


def mlp_kinds(conf: dict) -> list:
    return ["dense" if i < conf["first_k_dense_replace"] else "sparse"
            for i in range(conf["num_hidden_layers"])]


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    types = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    router = _router_width(conf)
    lin = conf["linear_attn_config"]
    run = conf["run"]
    kw = dict(vocab_size=conf["vocab_size"],
              num_layers=conf["num_hidden_layers"],
              embed_dim=conf["hidden_size"],
              num_heads=conf["num_attention_heads"],
              num_kv_heads=conf["num_key_value_heads"],
              attn_head_dim=conf["head_dim"],
              mlp_dim=conf["intermediate_size"],
              moe_mlp_dim=conf["moe_intermediate_size"], max_len=max_len,
              rope_theta=float(conf["rope_theta"]), tie_embeddings=False,
              rope_global=bool(conf["use_rope"]),
              attn_gate=bool(conf["use_gqa_gate"]),
              dtype=types[run["compute_dtype"]],
              attention_impl=run.get("attention", "auto"),
              norm_eps=float(conf["rms_norm_eps"]),
              layer_attn=tuple(layer_kinds(conf)),
              layer_mlp=tuple(mlp_kinds(conf)), moe_experts=router,
              moe_held=(conf["n_routed_experts"]
                        if conf["n_routed_experts"] < router else 0),
              moe_top_k=conf["num_experts_per_tok"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=bool(conf["norm_topk_prob"]),
              moe_router="sigmoid", moe_select_bias=True,
              moe_routed_scale=float(conf["routed_scaling_factor"]),
              moe_shared_dim=(conf["n_shared_experts"]
                              * conf["moe_intermediate_size"]),
              kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
              kda_conv=lin["short_conv_kernel_size"],
              kda_chunk=run.get("kda_chunk", 64),
              kda_state_dtype=types[run.get("kda_state_dtype", "float32")],
              kda_neg_eigval=bool(conf["kda_allow_neg_eigval"]))
    kw.update(overrides)
    return TransformerConfig(**kw)


BIAS_SCALE = 0.05


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device from the seed, one layer per
    jitted call and cast inside it, ``layer_<i>``, as
    ``archs/kimi_linear.py`` makes them.

    The program's own initialisers with PR 26's corrections (PERF.md
    section 6): each expert matrix lecun-normal BY ITSELF, norm scales 1
    + 0.1 normal, the selection bias ``BIAS_SCALE`` normal (small beside
    the scores' spread, not zero), embedding rows unit normal under an
    untied lecun-normal head.  So that every published switch is
    exercised: the convolutions' weights 0.5 normal, ``dt_bias`` the
    inverse softplus of a log-uniform step in [1e-3, 1e-1] and ``A_log =
    log(uniform[1, 16])`` (``KDAMixer``'s own: a channel forgets in one
    token or in a thousand); beta twice a sigmoid of a unit-normal logit
    (half of the betas lie above 1: the transition's eigenvalue is
    negative there); the gate a sigmoid of a unit-normal logit (the
    middle half of the gates lies in 0.34-0.66, a quarter beyond each
    end: a gate left out or held at 1/2 is another model)."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block

    if not split_layers:
        raise ValueError("a stack whose layers differ has no stacked layout")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]
    D, V = cfg.embed_dim, cfg.vocab_size

    def cast(path, a, key):
        name = path[-1].key
        normal = jax.random.normal(key, a.shape, jnp.float32)
        if name == "scale":
            a = 1.0 + 0.1 * normal
        elif name == "gate_bias":
            a = BIAS_SCALE * normal
        elif name == "conv_w":
            a = 0.5 * normal
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


# -- the reference ------------------------------------------------------------
def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


def _gated(y, w_gate, w_in, w_out):
    return (jax.nn.silu(y @ _f32(w_gate)) * (y @ _f32(w_in))) @ _f32(w_out)


def _router_width(conf: dict) -> int:
    return conf.get("router_experts", conf["n_routed_experts"])


def route(y, p, conf, nudge=None):
    """``(weight [T, E], chosen [T, k])``: every token's gates as a
    dense matrix over ALL the router's experts, and the experts it
    chose: the k largest of score + bias, the scores of those
    renormalised and scaled.  ``nudge`` [T, E] is added to what CHOOSES,
    never to what weighs: how a caller has a near-tie between two
    experts resolved the other way for one token
    (``tie_aware_shortfall``)."""
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


def held_experts(conf: dict, p, y, held=None, nudge=None):
    """The experts ``held`` (module docstring) ALONE on ``y [T, D]``:
    this share's partial sum, the shared expert not in it.  ``(out [T,
    D], chosen)``.  ``p``'s expert matrices are those of the share."""
    lo, hi = held or (0, conf["n_routed_experts"])
    weight, chosen = route(y, p, conf, nudge)

    def expert(acc, e):
        w_gate, w_in, w_out, w = e
        return acc + _gated(y, w_gate, w_in, w_out) * w[:, None], None

    out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                          (p["w_gate"], p["w_in"], p["w_out"],
                           weight[:, lo:hi].T))
    return out, chosen


def moe_mlp(conf: dict, p, y, held=None, nudge=None):
    """The expert block on ``y [T, D]``: ``held_experts`` and the shared
    expert.  ``(out [T, D], chosen, the held experts' partial sum)``."""
    routed, chosen = held_experts(conf, p, y, held, nudge)
    shared = _gated(y, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                    p["shared_out"]["kernel"])
    return routed + shared, chosen, routed


def kda_mixer(conf: dict, p, y):
    """The KDA mixer on ``y [B, L, D]`` (normed input): the plain
    recurrence, one token at a time, from a zero state.  ``(out [B, L,
    D], the state after the last token [B, H, R, R])``."""
    lin = conf["linear_attn_config"]
    H, R, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    di = H * R
    b, l, _ = y.shape
    qkv, f, z, beta = jnp.split(y @ _f32(p["in_proj"]["kernel"]),
                                [3 * di, 3 * di + R, 3 * di + 2 * R], axis=-1)
    w = _f32(p["conv_w"])                                      # [K, 3 di]
    padded = jnp.pad(qkv, ((0, 0), (K - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, i:i + l] * w[i] for i in range(K)))
    q, k, v = (a.reshape(b, l, H, R) for a in jnp.split(qkv, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * R ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    g = (jax.nn.softplus(f @ _f32(p["f_proj"]["kernel"]) + _f32(p["dt_bias"])
                         ).reshape(b, l, H, R)
         * -jnp.exp(_f32(p["A_log"]))[:, None])
    beta = jax.nn.sigmoid(beta)                                # [B, L, H]
    if conf["kda_allow_neg_eigval"]:
        beta = 2.0 * beta

    def step(s, t):
        qt, kt, vt, gt, bt = t
        s = s * jnp.exp(gt)[..., None]
        u = vt - jnp.einsum("bhkv,bhk->bhv", s, kt)
        s = s + (bt[..., None] * kt)[..., None] * u[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, qt)

    last, o = jax.lax.scan(
        step, jnp.zeros((b, H, R, R), jnp.float32),
        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    o = _rmsnorm(jnp.moveaxis(o, 0, 1), p["o_norm"]["scale"],
                 float(conf["rms_norm_eps"]))
    gate = jax.nn.sigmoid(z @ _f32(p["g_proj"]["kernel"]))
    return (o.reshape(b, l, di) * gate) @ _f32(p["o_proj"]["kernel"]), last


def _rotate(x, positions, theta: float):
    """Rotary embedding over the whole head (``partial_rotary_factor``
    1), half-split pairs: what ``use_rope: true`` would mean, kept so
    that the wrong variant can be read (``chip_solar_variants.py``)."""
    d = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(d, dtype=jnp.float32) / d)
    ang = positions[..., None, None].astype(jnp.float32) * freq
    x1, x2 = x[..., :d], x[..., d:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def gqa_project(conf: dict, p, y, positions=None):
    """``(q [B, L, H, Dh], k, v [B, L, Hk, Dh], gate [B, L, H Dh] or
    None)`` of the gated GQA mixer on ``y [B, L, D]``."""
    H, Hk, Dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    b, l, _ = y.shape
    q, k, v = jnp.split(y @ _f32(p["attn_qkv"]["kernel"]),
                        [H * Dh, (H + Hk) * Dh], axis=-1)
    q, k, v = (q.reshape(b, l, H, Dh), k.reshape(b, l, Hk, Dh),
               v.reshape(b, l, Hk, Dh))
    if conf["use_rope"]:
        pos = (jnp.broadcast_to(jnp.arange(l), (b, l)) if positions is None
               else positions)
        q = _rotate(q, pos, float(conf["rope_theta"]))
        k = _rotate(k, pos, float(conf["rope_theta"]))
    gate = (jax.nn.sigmoid(y @ _f32(p["attn_gate"]["kernel"]))
            if conf["use_gqa_gate"] else None)
    return q, k, v, gate


def gqa_attend(conf: dict, q, k, v, first: int):
    """Causal softmax attention of queries ``q [B, n, H, Dh]`` at
    positions ``first .. first + n - 1`` over ALL of ``k, v [B, L, Hk,
    Dh]``, grouped, float32: ``[B, n, H Dh]``."""
    b, n, H, Dh = q.shape
    l, Hk = k.shape[1], k.shape[2]
    qg = q.reshape(b, n, Hk, H // Hk, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) * Dh ** -0.5
    i, j = first + jnp.arange(n)[:, None], jnp.arange(l)[None, :]
    s = jnp.where(j <= i, s, -jnp.inf)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v)
    return o.reshape(b, n, H * Dh)


def gqa_mixer(conf: dict, p, y, last: int | None = None):
    """The gated GQA mixer on ``y [B, L, D]``: every visible row's
    score, no cache.  With ``last`` only the last ``last`` positions'
    outputs ``[B, last, D]`` (their scores alone are formed)."""
    l = y.shape[1]
    n = l if last is None else last
    q, k, v, gate = gqa_project(conf, p, y)
    o = gqa_attend(conf, q[:, l - n:], k, v, l - n)
    if gate is not None:
        o = o * gate[:, l - n:]
    return o @ _f32(p["attn_out"]["kernel"])


_MIXER_KEYS = ("num_attention_heads", "num_key_value_heads", "head_dim",
               "use_rope", "rope_theta", "use_gqa_gate",
               "kda_allow_neg_eigval", "rms_norm_eps")
_MLP_KEYS = ("n_routed_experts", "router_experts", "num_experts_per_tok",
             "norm_topk_prob", "routed_scaling_factor", "rms_norm_eps")


def _frozen(conf: dict, keys, lin: bool = False):
    """The configuration as a hashable static argument."""
    out = tuple((k, conf[k]) for k in keys if k in conf)
    if lin:
        c = conf["linear_attn_config"]
        out += (("linear_attn_config", tuple(
            (k, c[k]) for k in ("num_heads", "head_dim",
                                "short_conv_kernel_size"))),)
    return out


def _thaw(conf) -> dict:
    conf = dict(conf)
    if "linear_attn_config" in conf:
        conf["linear_attn_config"] = dict(conf["linear_attn_config"])
    return conf


@functools.partial(jax.jit, static_argnames=("conf", "kind"))
def _mixer(x, p, *, conf, kind):
    """``(x + Mixer(RMSNorm(x)), the normed input, the mixer's output, a
    KDA mixer's state after the last token or None)``."""
    conf = _thaw(conf)
    with jax.default_matmul_precision("highest"):
        y = _rmsnorm(x, p["attn_norm"]["scale"], float(conf["rms_norm_eps"]))
        out, state = (kda_mixer(conf, p["kda"], y) if kind == "kda"
                      else (gqa_mixer(conf, p, y), None))
        return x + out, y, out, state


@functools.partial(jax.jit, static_argnames=("conf", "held"))
def _mlp(x, p, nudge=None, *, conf, held):
    conf = dict(conf)
    with jax.default_matmul_precision("highest"):
        b, l, d = x.shape
        y = _rmsnorm(x, p["mlp_norm"]["scale"], float(conf["rms_norm_eps"]))
        if "moe" not in p:                      # a leading dense layer
            out = _gated(y, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                         p["mlp_out"]["kernel"])
            return x + out, None, y, out, None
        out, chosen, routed = moe_mlp(
            conf, p["moe"], y.reshape(b * l, d), held,
            None if nudge is None else nudge.reshape(b * l, -1))
        out = out.reshape(b, l, d)
        return (x + out, chosen.reshape(b, l, -1), y, out,
                routed.reshape(b, l, d))


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_scale, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_scale, eps) @ _f32(kernel)


def forward(conf: dict, params, ids, held=None, nudge=None):
    """``(hidden [B, L, D] before the last norm, chosen {sparse layer:
    [B, L, k]}, experts {sparse layer: (input, output, the held experts'
    part of the output)}, mixers {layer: (input, output, the layer's
    input before its norm, a KDA mixer's final state)})``.  ``nudge``
    {layer: [B, L, E]} as ``route`` takes it."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    kinds = layer_kinds(conf)
    routes, experts, mixers = {}, {}, {}
    for i in range(conf["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        before = x
        x, y, out, state = _mixer(
            x, p, conf=_frozen(conf, _MIXER_KEYS, lin=True), kind=kinds[i])
        mixers[i] = (y, out, before, state)
        x, chosen, y, out, routed = _mlp(
            x, p, (nudge or {}).get(i), conf=_frozen(conf, _MLP_KEYS),
            held=held)
        if chosen is not None:
            routes[i], experts[i] = chosen, (y, out, routed)
    return x, routes, experts, mixers


def reference(conf: dict, params, ids, held=None, nudge=None) -> dict:
    """The full forward pass: ``logits`` [B, L, V] float32, ``chosen``,
    ``experts`` and ``mixers`` (``forward``)."""
    x, chosen, experts, mixers = forward(conf, params, ids, held, nudge)
    return {"logits": _head(x, params["final_norm"]["scale"],
                            params["lm_head"]["kernel"],
                            eps=float(conf["rms_norm_eps"])),
            "chosen": chosen, "experts": experts, "mixers": mixers}


def logits(conf: dict, params, ids, held=None):
    """[B, L, V] float32 logits of the full forward pass."""
    return reference(conf, params, ids, held)["logits"]


# -- the program's block, for the comparison ---------------------------------
def program_forward(cfg, params, ids):
    """The PROGRAM's block over ``ids``: ``edl_tpu``'s ``Block`` layer by
    layer, its final norm and head, in ``cfg``'s compute type (full
    forward: the chunked delta rule from a zero state, the gated
    attention over the call's own rows, no cache).  Returns ``(logits [B, L, V] float32,
    chosen {sparse layer: [B, L, k]})``, the experts each layer's float32
    router picked from the block's own ``mlp_norm`` output."""
    from edl_tpu.models.transformer import Block, RMSNorm

    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = jnp.take(params["tok_embed"]["embedding"], ids, axis=0).astype(
        cfg.dtype)
    routes = {}

    # one compiled program a KIND of layer (the first of the kind stands
    # for all of them)
    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(p, x, like):
        (x, _), seen = Block(cfg, like).apply(
            {"params": p}, x, pos, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "mlp_norm")
        if "moe" not in p:
            return x, None
        y = seen["intermediates"]["mlp_norm"]["__call__"][0]
        pick = (jax.nn.sigmoid(_f32(y) @ _f32(p["moe"]["gate"]))
                + _f32(p["moe"]["gate_bias"]))
        return x, jax.lax.top_k(pick, cfg.moe_top_k)[1]

    kinds = [(cfg.attn_kind(i), cfg.mlp_kind(i))
             for i in range(cfg.num_layers)]
    for i in range(cfg.num_layers):
        x, chosen = layer(params[f"layer_{i}"], x, kinds.index(kinds[i]))
        if chosen is not None:
            routes[i] = chosen
    x = RMSNorm(cfg.dtype, cfg.norm_eps).apply(
        {"params": params["final_norm"]}, x)
    return _f32(x @ params["lm_head"]["kernel"].astype(cfg.dtype)), routes


def program_experts(cfg, moe_params, y, shared: bool = True):
    """The PROGRAM's expert layer alone (``ops/moe.py``'s ``MoEMLP`` as
    ``Block`` builds it: router, held experts, shared expert) on ``y``
    [B, L, D]; without ``shared`` the held experts' partial sum alone."""
    from edl_tpu.ops.moe import MoEMLP

    layer = MoEMLP(num_experts=cfg.moe_experts, mlp_dim=cfg.expert_dim,
                   top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity,
                   dtype=cfg.dtype, gated=cfg.moe_gated,
                   norm_topk=cfg.moe_norm_topk, router=cfg.moe_router,
                   select_bias=cfg.moe_select_bias,
                   routed_scale=cfg.moe_routed_scale,
                   shared_dim=cfg.moe_shared_dim if shared else 0,
                   held=cfg.moe_held)
    (out, _), _ = jax.jit(lambda p, y: layer.apply(
        {"params": p}, y, mutable=["intermediates"]))(
            moe_params, y.astype(cfg.dtype))
    return _f32(out)


def program_mixer(cfg, kda_params, y):
    """The PROGRAM's KDA mixer alone (``KDAMixer``: projections,
    convolutions, the chunked delta rule, gate and norm) on ``y``."""
    from edl_tpu.models.transformer import KDAMixer

    return _f32(jax.jit(lambda p, y: KDAMixer(cfg).apply({"params": p}, y))(
        kda_params, y.astype(cfg.dtype)))


def _fresh(module, *args):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(module.init, jax.random.key(0), *args)["cache"])


def program_state(cfg, kda_params, y, chunk: int):
    """The PROGRAM's KDA mixer alone THROUGH ITS CACHE on ``y`` [1, L,
    D]: the first ``chunk`` positions in one call (the chunked form, the
    state left in the cache) and every later position one token at a
    time from the cached state (on the chip the ``kda_step`` kernel), as
    a slot of the engine does.  Returns the state the cache holds at the
    end, [H, R, R] float32."""
    from edl_tpu.models.transformer import KDAMixer

    mixer = KDAMixer(dataclasses.replace(cfg, decode=True))

    @jax.jit
    def run(p, y):
        _, mut = mixer.apply({"params": p, "cache": _fresh(mixer, y[:, :1])},
                             y[:, :chunk], mutable=["cache"])

        def one(cache, yt):
            _, mut = mixer.apply({"params": p, "cache": cache}, yt[:, None],
                                 mutable=["cache", "intermediates"])
            return mut["cache"], None

        cache, _ = jax.lax.scan(one, mut["cache"],
                                jnp.moveaxis(y[:, chunk:], 1, 0))
        return _f32(cache["kda_state"][0])

    return run(kda_params, y[:1].astype(cfg.dtype))


def _attention_only(cfg, layer: int):
    """The PROGRAM's attention mixer of layer ``layer`` as a module of
    its own: ``Block._attention`` on an already normed input, over the
    layer's own parameter names (``attn_qkv``, ``attn_gate``,
    ``attn_out``) and, in a decode model, its own cache."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block

    class AttentionOnly(Block):
        @nn.compact
        def __call__(self, y, positions, token_mask=None):
            return self._attention(y, positions, token_mask, "global")[0]

    return AttentionOnly(cfg, layer)


def program_attention(cfg, layer_params, y, layer: int = 0):
    """The PROGRAM's gated GQA mixer alone (``Block._attention`` in a
    full forward: projections, attention over the call's rows, gate,
    output matrix) on ``y`` [B, L, D]."""
    pos = jnp.broadcast_to(jnp.arange(y.shape[1]), y.shape[:2])
    mixer = _attention_only(cfg, layer)
    return _f32(jax.jit(lambda p, y: mixer.apply({"params": p}, y, pos))(
        layer_params, y.astype(cfg.dtype)))


def long_rows(key, at: int, n: int, width: int):
    """Rows ``at .. at + n - 1`` of the seeded unit-normal input of the
    long-prefix comparison, a block of 1,024 rows a key: neither side
    ever holds the whole input."""
    blk = 1024
    out = [jax.random.normal(jax.random.fold_in(key, j), (blk, width),
                             jnp.float32)
           for j in range(at // blk, -(-(at + n) // blk))]
    return jnp.concatenate(out)[at % blk:at % blk + n]


def program_long(cfg, layer_params, key, prefix: int, chunk: int, steps: int,
                 layer: int = 0, tail: int = 64):
    """The PROGRAM's gated GQA mixer alone THROUGH ITS CACHE against a
    prefix of ``prefix`` rows of ``long_rows``: the prefix in calls of
    ``chunk`` (each attends the slab up to its last position: where
    ``ops/decode_attention.prefix_tiled`` holds, and at the cell's
    shapes it does, the TILED chunk path), then ``steps`` one-token
    calls (on the chip ``decode_append`` and ``decode_attend``).
    Returns ``(the last chunk's last ``tail`` outputs [tail, D], the
    steps' outputs [steps, D])``, float32."""
    if prefix % chunk:
        raise ValueError(f"a prefix of {prefix} rows in chunks of {chunk}")
    total = prefix + steps
    mixer = _attention_only(dataclasses.replace(
        cfg, decode=True, max_len=-(-total // 128) * 128), layer)
    one = jnp.zeros((1, 1), jnp.int32)
    cache = jax.jit(lambda: _fresh(mixer, jnp.zeros(
        (1, 1, cfg.embed_dim), cfg.dtype), one))()

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(p, cache, rows, start):
        out, mut = mixer.apply(
            {"params": p, "cache": cache}, rows[None].astype(cfg.dtype),
            start + jnp.arange(rows.shape[0])[None],
            mutable=["cache", "intermediates"])
        return out[0], mut["cache"]

    at, last, outs = 0, None, []
    while at < total:
        n = chunk if at < prefix else 1
        out, cache = run(layer_params, cache,
                         long_rows(key, at, n, cfg.embed_dim),
                         jnp.asarray(at, jnp.int32))
        at += n
        if at == prefix:
            last = out[-tail:]
        elif at > prefix:
            outs.append(out[0])
    return _f32(last), _f32(jnp.stack(outs))


def reference_long(conf: dict, layer_params, key, prefix: int, steps: int,
                   tail: int = 64, block: int = 4096, queries: int = 16):
    """The reference's gated GQA mixer on the same ``long_rows``: keys
    and values of all ``prefix + steps`` rows, projected a block of rows
    at a time, and the outputs of the last ``tail`` rows of the prefix
    and of the ``steps`` rows after it, ``queries`` at a time (their
    scores alone are formed: [H, queries, prefix + steps] float32)."""
    D = conf["hidden_size"]
    total = prefix + steps

    @jax.jit
    def project(p, y):
        with jax.default_matmul_precision("highest"):
            return gqa_project(conf, p, y[None])

    @functools.partial(jax.jit, static_argnames=("first",))
    def attend(p, q, k, v, gate, *, first):
        with jax.default_matmul_precision("highest"):
            o = gqa_attend(conf, q, k, v, first)
            if gate is not None:
                o = o * gate
            return (o @ _f32(p["attn_out"]["kernel"]))[0]

    ks, vs, qs, gs = [], [], [], []
    for at in range(0, total, block):
        n = min(block, total - at)
        q, k, v, gate = project(layer_params, long_rows(key, at, n, D))
        ks.append(k)
        vs.append(v)
        keep = slice(max(prefix - tail - at, 0), n)
        if at + n > prefix - tail:
            qs.append(q[:, keep])
            gs.append(None if gate is None else gate[:, keep])
    k, v = jnp.concatenate(ks, 1), jnp.concatenate(vs, 1)
    q = jnp.concatenate(qs, 1)
    gate = None if gs[0] is None else jnp.concatenate(gs, 1)
    outs = [attend(layer_params, q[:, j:j + queries], k, v,
                   None if gate is None else gate[:, j:j + queries],
                   first=prefix - tail + j)
            for j in range(0, tail + steps, queries)]
    out = jnp.concatenate(outs)
    return out[:tail], out[tail:]


def program_cached(cfg, params, ids, chunk: int, steps: int):
    """The PROGRAM's block THROUGH ITS CACHE over ``ids`` [1, L]: a
    decode model (no engine) prefills all but the last ``steps`` tokens
    in chunks of ``chunk`` with state and key and value rows carried,
    then takes the last ``steps`` tokens one at a time (on the chip
    ``kda_step``, ``decode_append`` and ``decode_attend``).  Returns
    those steps' logits [steps, V] float32."""
    from edl_tpu.models.transformer import TransformerLM

    L = ids.shape[1]
    model = TransformerLM(dataclasses.replace(
        cfg, decode=True, attention_impl="dense",
        max_len=-(-L // 128) * 128))
    cache = jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), jax.eval_shape(
            lambda: model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32),
                               positions=jnp.zeros((1, 1), jnp.int32))
        )["cache"]))()

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(params, cache, tokens, start):
        out, mut = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=start + jnp.arange(tokens.shape[1])[None],
            mutable=["cache", "intermediates"])
        return out[0, -1], mut["cache"]

    at, out = 0, []
    while at < L:
        n = min(chunk, L - steps - at) if at < L - steps else 1
        row, cache = run(params, cache, ids[:, at:at + n],
                         jnp.asarray(at, jnp.int32))
        at += n
        if at > L - steps:
            out.append(row)
    return jnp.stack(out)


def held_pairs(conf: dict, chosen: dict, upto: int | None = None) -> int:
    """The host's recount: of the reference router's (token, expert)
    pairs over the first ``upto`` positions, those that land on the
    experts held here, summed over the sparse layers."""
    import numpy as np
    return int(sum((np.asarray(c)[:, :upto] < conf["n_routed_experts"]).sum()
                   for c in chosen.values()))


def _selection_scores(gate, bias, y):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(y @ _f32(gate)) + _f32(bias)


def held_swaps(v, held: int, top_k: int, delta: float) -> list:
    """``[(gap, out, in)]``, nearest tie first: the swaps of one chosen
    expert for one unchosen one that change WHICH HELD EXPERTS one token
    computes, among the pairs whose selection scores ``v`` [E] lie
    within ``delta`` of each other (``archs/exaone_moe.held_swaps``,
    copied: an architecture's module stands alone)."""
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
    one sparse layer each (``archs/exaone_moe.tie_aware_shortfall``:
    the same sigmoid router with a selection bias and a held share, so
    the same heavy tail; PERF.md section 6, PR 30).  The search runs
    only where ``plain`` is over ``limit``, stops at the first routing
    under which the token is within ``limit``, and spends at most
    ``passes`` reference passes.

    Returns ``{"plain", "shortfall", "swaps" [(layer, out, in, gap)],
    "passes"}``."""
    import numpy as np

    def column(r):
        return (np.asarray(r["logits"][0, at]),
                {i: e[0][0, at] for i, e in r["experts"].items()})

    def short(row):
        return float((row.max() - row[token]) / row.std())

    held, k = conf["n_routed_experts"], conf["num_experts_per_tok"]
    width = _router_width(conf)
    row, into_layers = column(ref)
    found = {"plain": short(row), "shortfall": short(row), "swaps": [],
             "passes": 0}
    if found["plain"] <= limit:
        return found
    level = [((), into_layers)]         # (swaps taken, that pass's inputs)
    for _ in range(depth):
        nxt = []
        for swaps, inputs in level:
            cands = []
            for i, y in inputs.items():
                if swaps and i <= swaps[-1][0]:
                    continue            # a pair of layers once, in order
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


def _rel(diff, want, axes=-1):
    import numpy as np
    return np.asarray(jnp.linalg.norm(diff, axis=axes)
                      / jnp.maximum(jnp.linalg.norm(want, axis=axes), 1e-30)
                      ).reshape(-1)


def slow_heads(kda_params):
    """The tenth of a KDA mixer's heads (one at least) whose state
    decays slowest at a zero projection: the smallest ``exp(A_log) *
    mean softplus(dt_bias)``.  They remember hundreds of steps."""
    heads = kda_params["A_log"].shape[0]
    rate = jnp.exp(_f32(kda_params["A_log"])) * jax.nn.softplus(
        _f32(kda_params["dt_bias"])).reshape(heads, -1).mean(-1)
    return jnp.argsort(rate)[:max(1, heads // 10)]


CACHE_STEPS = 16


def block_agreement(conf: dict, params, ids, ref: dict, *, cfg=None,
                    program_params=None, tag: str = "") -> dict:
    """The program's block (``cfg`` and ``program_params`` let a
    deliberately wrong variant stand in) against ``reference``'s ``ref``
    on the same ``ids``, as ``archs/kimi_linear.py`` compares, and
    prints.  Every error is the norm of (program - reference) over the
    norm of the reference's output, a token, each part fed the
    reference's own input to it:

    ``mixer_error`` [KDA layers * B * L]: every KDA mixer ALONE.
    ``attention_error`` [GQA layers * B * L]: every gated GQA mixer
    alone in a full forward (the long prefix through the cache is
    ``long_prefix_agreement``'s).
    ``expert_error`` [sparse layers * B * L], ``routed_error``: every
    expert layer alone, and with the shared expert out of both sides.
    ``state_error`` [KDA layers * slow heads]: every KDA mixer alone
    THROUGH ITS CACHE (``program_state``: one chunk, then one-token
    updates), the state the cache holds at the end against the
    reference recurrence's, a head, for each layer's ``slow_heads``.
    ``logit_error_sigma`` [B * L]: the whole block at the level of
    logits, the root mean square over the vocabulary of (program -
    reference) in standard deviations of the reference's logits there.
    ``cache_error_sigma`` [``CACHE_STEPS``]: the same for the block
    THROUGH ITS CACHE (``program_cached``) at the probe's last
    positions.
    ``expert_sets_differ``, ``held_pairs``: as the other expert cells."""
    import numpy as np

    cfg = cfg or transformer_config(conf, max_len=ids.shape[1], remat=False,
                                    attention_impl="dense")
    pp = params if program_params is None else program_params
    own, picked = program_forward(cfg, pp, ids)
    want = ref["logits"]
    differ = float(np.mean([
        np.asarray((jnp.sort(picked[i], -1) != jnp.sort(c, -1)).any(-1))
        for i, c in ref["chosen"].items()]))
    err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(own - want), -1))
                     / jnp.std(want, -1)).reshape(-1)
    experts = np.concatenate([
        _rel(program_experts(cfg, pp[f"layer_{i}"]["moe"], y) - out, out)
        for i, (y, out, _) in ref["experts"].items()])
    routed = np.concatenate([
        _rel(program_experts(cfg, pp[f"layer_{i}"]["moe"], y, shared=False)
             - part, part) for i, (y, _, part) in ref["experts"].items()])
    kda = [(pp[f"layer_{i}"]["kda"], slow_heads(params[f"layer_{i}"]["kda"]),
            m) for i, m in ref["mixers"].items() if cfg.attn_kind(i) == "kda"]
    gqa = [(i, m) for i, m in ref["mixers"].items()
           if cfg.attn_kind(i) == "global"]
    mixers = np.concatenate([_rel(program_mixer(cfg, p, y) - out, out)
                             for p, _, (y, out, _, _) in kda])
    chunk = conf["run"]["prefill_chunk"]
    states = np.concatenate([
        _rel((program_state(cfg, p, y, chunk) - last[0])[slow],
             last[0][slow], axes=(-2, -1))
        for p, slow, (y, _, _, last) in kda])
    attention = np.concatenate([
        _rel(program_attention(cfg, pp[f"layer_{i}"], y, i) - out, out)
        for i, (y, out, _, _) in gqa])
    cached = program_cached(cfg, pp, ids[:1], chunk, CACHE_STEPS)
    tail = want[0, -CACHE_STEPS:]
    cache_err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(cached - tail), -1))
                           / jnp.std(tail, -1))
    pairs = held_pairs(conf, ref["chosen"])
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: KDA mixers alone, error over norm, median "
          f"{np.median(mixers):.5f} max {mixers.max():.5f} over "
          f"{mixers.size} (token, layer) pairs; gated GQA mixers alone "
          f"median {np.median(attention):.5f} max {attention.max():.5f} over "
          f"{attention.size}; expert layers "
          f"alone median {np.median(experts):.5f} mean {experts.mean():.5f} "
          f"over {experts.size}, their held experts alone median "
          f"{np.median(routed):.5f}; the KDA state after one chunk of {chunk} "
          f"and {max(0, ids.shape[1] - chunk)} one-token updates, error over "
          f"norm a slow head, median {np.median(states):.5f} max "
          f"{states.max():.5f} over {states.size}; logits, median "
          f"{np.median(err):.5f} mean {err.mean():.5f} max {err.max():.5f} "
          f"sigma over {err.size} positions; through the cache (chunks of "
          f"{chunk}, then {CACHE_STEPS} one-token steps) median "
          f"{np.median(cache_err):.5f} max {cache_err.max():.5f} sigma; "
          f"expert sets differ in {100 * differ:.3f}% of the (token, layer) "
          f"pairs; {pairs} pairs on held experts", flush=True)
    return {"mixer_error": mixers, "attention_error": attention,
            "expert_error": experts, "routed_error": routed,
            "state_error": states, "logit_error_sigma": err,
            "cache_error_sigma": cache_err, "expert_sets_differ": differ,
            "held_pairs": pairs}


LONG_TAIL = 64


def long_prefix_agreement(conf: dict, params, seed: int, *, cfg=None,
                          program_params=None, tag: str = "") -> dict:
    """The program's gated GQA mixers THROUGH THE CACHE against a prefix
    of ``run.long_prefix`` rows (65,536: the median first turn), each
    against the reference's attention over the same rows
    (``reference_long``), error over norm a position:

    ``long_chunk_error`` [GQA layers * ``LONG_TAIL``]: the last
    ``LONG_TAIL`` positions of the prefix's last chunk, which the
    program computes on the multi-token path that reads the LIVE PREFIX
    (tiled where ``ops/decode_attention.prefix_tiled`` holds);
    ``long_step_error`` [GQA layers * ``CACHE_STEPS``]: the one-token
    steps after it (on the chip ``decode_append`` / ``decode_attend``).

    It needs 0.6 GB of float32 keys and values and 0.3 GB of scores on
    the reference's side and one lane's slabs on the program's: the
    runner calls it before the engine takes the memory."""
    import numpy as np

    run = conf["run"]
    prefix, chunk = run["long_prefix"], run["prefill_chunk"]
    cfg = cfg or transformer_config(conf, max_len=prefix + 128, remat=False,
                                    attention_impl="dense")
    pp = params if program_params is None else program_params
    key = jax.random.key(seed % (1 << 31))
    chunks, steps = [], []
    for i, kind in enumerate(layer_kinds(conf)):
        if kind != "global":
            continue
        want_c, want_s = reference_long(conf, params[f"layer_{i}"], key,
                                        prefix, CACHE_STEPS, LONG_TAIL)
        got_c, got_s = program_long(cfg, pp[f"layer_{i}"], key, prefix,
                                    chunk, CACHE_STEPS, i, LONG_TAIL)
        chunks.append(_rel(got_c - want_c, want_c))
        steps.append(_rel(got_s - want_s, want_s))
    chunks, steps = np.concatenate(chunks), np.concatenate(steps)
    print(f"[bench] gated GQA mixers{tag} through the cache against a "
          f"prefix of {prefix} rows: the last {LONG_TAIL} positions of the "
          f"last chunk of {chunk} (the multi-token path over the live "
          f"prefix) median {np.median(chunks):.5f} max {chunks.max():.5f}; "
          f"{CACHE_STEPS} one-token steps after it median "
          f"{np.median(steps):.5f} max {steps.max():.5f}", flush=True)
    return {"long_chunk_error": chunks, "long_step_error": steps}


# -- what the algorithms need, from shapes alone ------------------------------
def kda_layers(conf: dict) -> int:
    return layer_kinds(conf).count("kda")


def gqa_layers(conf: dict) -> int:
    return layer_kinds(conf).count("global")


def sparse_layers(conf: dict) -> int:
    return mlp_kinds(conf).count("sparse")


def _kda_inner(conf: dict) -> int:
    lin = conf["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def expert_params(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def kda_matmul_params(conf: dict) -> int:
    d, lin = conf["hidden_size"], conf["linear_attn_config"]
    r, di = lin["head_dim"], _kda_inner(conf)
    return d * (3 * di + 2 * r + lin["num_heads"]) + 2 * r * di + di * d


def kda_params(conf: dict) -> int:
    """A KDA mixer whole: the projections, the convolutions, dt_bias,
    A_log and the output norm's scale."""
    lin = conf["linear_attn_config"]
    di = _kda_inner(conf)
    return (kda_matmul_params(conf) + 3 * di * lin["short_conv_kernel_size"]
            + di + lin["num_heads"] + lin["head_dim"])


def gqa_matmul_params(conf: dict) -> int:
    """A gated GQA mixer: q, k, v, the gate and the output matrix."""
    d, h, hk, dh = (conf["hidden_size"], conf["num_attention_heads"],
                    conf["num_key_value_heads"], conf["head_dim"])
    gate = d * h * dh if conf["use_gqa_gate"] else 0
    return d * (h + 2 * hk) * dh + gate + h * dh * d


def kv_row_bytes(conf: dict, itemsize: int = 2) -> int:
    """One position's key and value rows in ONE gated GQA layer."""
    return 2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize


def shared_matmul_params(conf: dict) -> int:
    """Read by every token, all layers together: the mixers, the dense
    layers' MLPs, the routers and the shared experts."""
    d = conf["hidden_size"]
    dense = conf["num_hidden_layers"] - sparse_layers(conf)
    return (kda_layers(conf) * kda_matmul_params(conf)
            + gqa_layers(conf) * gqa_matmul_params(conf)
            + dense * 3 * d * conf["intermediate_size"]
            + sparse_layers(conf)
            * (d * _router_width(conf) + 3 * d * conf["n_shared_experts"]
               * conf["moe_intermediate_size"]))


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Of the gated GQA layers: the only cache that grows with the
    context, a key row and a value row a KV head a token a layer."""
    return kv_row_bytes(conf, itemsize) * gqa_layers(conf)


def state_bytes_per_slot(conf: dict, itemsize: int = 2,
                         state_itemsize: int = 4) -> int:
    """A slot's recurrent state in all the KDA layers, whatever the
    context's length: S [H, R, R] float32 and the convolutions' last
    ``short_conv_kernel_size - 1`` inputs."""
    lin = conf["linear_attn_config"]
    di = _kda_inner(conf)
    return kda_layers(conf) * (
        di * lin["head_dim"] * state_itemsize
        + (lin["short_conv_kernel_size"] - 1) * 3 * di * itemsize)


def param_count(conf: dict) -> int:
    """Every parameter this device holds (``n_routed_experts`` routed
    experts a sparse layer, the router and its bias whole, the vocabulary slice
    for the embedding and for the head)."""
    d = conf["hidden_size"]
    dense = conf["num_hidden_layers"] - sparse_layers(conf)
    return (2 * conf["vocab_size"] * d + d
            + kda_layers(conf) * kda_params(conf)
            + gqa_layers(conf) * gqa_matmul_params(conf)
            + conf["num_hidden_layers"] * 2 * d
            + dense * 3 * d * conf["intermediate_size"]
            + sparse_layers(conf)
            * (d * _router_width(conf) + _router_width(conf)
               + 3 * d * conf["n_shared_experts"]
               * conf["moe_intermediate_size"]
               + conf["n_routed_experts"] * expert_params(conf)))


def step_min_bytes(conf: dict, experts_touched: float, live_tokens: float,
                   live_slots: float, itemsize: int = 2) -> float:
    """What one decode token step must read (and write) at least: the
    mixers', routers', shared experts' and head's weights once, the held
    experts its batch touched (a layer's mean) in every sparse layer,
    the gated GQA layers' live rows, and each of ``live_slots`` slots'
    recurrent state read once and written once."""
    shared = (shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = sparse_layers(conf) * experts_touched * expert_params(conf)
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens
            + 2.0 * state_bytes_per_slot(conf, itemsize) * live_slots)


def long_attend_min(conf: dict, positions: float, calls: float,
                    itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of ``decode_attend`` for ``positions`` live
    positions (summed over live slots, token steps and gated GQA layers)
    read by ``calls`` (slot, token step, layer) calls: each position's
    key and value rows read once for the query heads of their group,
    every head's score against the key and its part in the value sum at
    2 FLOPs a multiply-add; a call's queries read and outputs written.
    8 FLOPs a byte at 8 query heads a KV head: memory bound."""
    h, dh = conf["num_attention_heads"], conf["head_dim"]
    return (positions * h * 4.0 * dh,
            positions * kv_row_bytes(conf, itemsize)
            + calls * 2 * h * dh * itemsize)


def prefix_chunk_flops(conf: dict, pairs: float) -> float:
    """The multi-token attention of ``pairs`` (query, visible row)
    pairs a gated GQA layer: every head's score and its part in the
    value sum, 2 FLOPs a multiply-add."""
    return pairs * conf["num_attention_heads"] * 4.0 * conf["head_dim"]


def prefix_chunk_bytes(conf: dict, rows_live: float, tokens: float,
                       itemsize: int = 2) -> float:
    """What that attention must read and write at least: the rows LIVE
    below each call's last position (``rows_live``, summed over calls
    and gated GQA layers), key and value once a call, and each of
    ``tokens`` (query, layer) pairs' queries read and outputs written."""
    h, dh = conf["num_attention_heads"], conf["head_dim"]
    return (rows_live * kv_row_bytes(conf, itemsize)
            + tokens * 2 * h * dh * itemsize)


def kda_token_flops(conf: dict) -> float:
    """The recurrence of one token in ONE KDA layer beside its matmuls:
    decay, correction, rank-one update and readout at 2 FLOPs a state
    element each."""
    lin = conf["linear_attn_config"]
    return 8.0 * lin["num_heads"] * lin["head_dim"] ** 2


def token_matmul_flops(conf: dict, held_share: float, head: bool) -> float:
    """2 FLOPs a matmul weight for one token through the stack: mixers,
    routers and shared experts whole, ``num_experts_per_tok`` routed
    experts times the share of the routed pairs that land here, and
    (``head``) the output head."""
    routed = (sparse_layers(conf) * conf["num_experts_per_tok"] * held_share
              * expert_params(conf))
    return 2.0 * (shared_matmul_params(conf) + routed
                  + (conf["hidden_size"] * conf["vocab_size"] if head else 0))


def step_flops(conf: dict, slot_steps: float, live_rows: float,
               held_share: float) -> float:
    """The model FLOPs of ``slot_steps`` live (slot, token step) pairs
    whose gated GQA layers attended ``live_rows`` positions in all (one
    layer's worth): the matmuls with the head, the delta rule's update a
    KDA layer, and every head's score and value sum a visible row."""
    h, dh = conf["num_attention_heads"], conf["head_dim"]
    return (slot_steps * (token_matmul_flops(conf, held_share, True)
                          + kda_layers(conf) * kda_token_flops(conf))
            + live_rows * gqa_layers(conf) * h * 4.0 * dh)


def prefill_flops(conf: dict, tokens: float, pairs: float, rows: float,
                  held_share: float) -> float:
    """The model FLOPs of the multi-token programs for ``tokens`` real
    tokens that saw ``pairs`` (query, visible row) pairs a gated GQA
    layer and sampled at ``rows`` rows: the matmuls without the head at
    every token, the delta rule's update, the attention, and the head
    at the sampling rows alone."""
    return (tokens * (token_matmul_flops(conf, held_share, False)
                      + kda_layers(conf) * kda_token_flops(conf))
            + gqa_layers(conf) * prefix_chunk_flops(conf, pairs)
            + rows * 2.0 * conf["hidden_size"] * conf["vocab_size"])
