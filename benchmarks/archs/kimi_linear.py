"""Kimi-Linear-48B-A3B (``kimi_linear``) for the benchmark: configuration,
weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module, as
``archs/granite_moe_hybrid.py`` is: ``runners/serve_latent.py`` registers
it as ``model`` and its ``reference`` as ``reference``, and
``runners/serve.py`` then calls ``transformer_config``, ``init_params``
and ``logits`` exactly as it calls ``model.py`` and ``reference.py``.
``block_agreement`` is what the cell's ``correct`` also rests on.

The reference is the forward pass in plain ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: the delta rule one token at
a time, the latent attention un-absorbed, no cache, no kernels, no
chunks, no sort.  Nothing of ``edl_tpu`` is in it (the tier-1 tests in
``tests/test_kimi_linear.py`` hold the program to it at a toy size).
Its equations, with D ``hidden_size``, every layer ``x += Mixer(RMSNorm(
x))``, ``x += MLP(RMSNorm(x))``, a final RMSNorm and an untied head, no
positional embedding anywhere:

- KDA mixer (1-based layers ``linear_attn_config.kda_layers``; H heads
  of R = ``head_dim`` for keys and values): ``q, k, v = SiLU(conv(W x))``
  (depthwise causal convolutions over ``short_conv_kernel_size``
  positions, no bias); ``q``, ``k`` L2-normalised a head, ``q`` times
  ``R ** -0.5``; ``g = -exp(A_log[h]) * softplus(W_f2 (W_f1 x) +
  dt_bias)`` a key channel; ``beta = sigmoid(W_b x)``; state ``S [R, R]``
  a head: ``S' = Diag(exp(g)) S``, ``u = v - S'^T k``, ``S = S' + beta k
  u^T``, ``o = S^T q``; ``W_o (RMSNorm_head(o) * sigmoid(W_g2 (W_g1
  x)))``;
- MLA mixer (``full_attn_layers``): ``q = W_q x`` -> ``[H, nope +
  rope]``; ``W_kva x`` -> ``c' | k_pe``; ``c = RMSNorm(c')``; ``W_kvb c``
  -> ``[H, nope + v]`` = ``k_nope | v``; scores ``(q_nope . k_nope +
  q_pe . k_pe) / sqrt(nope + rope)``, causal, float32 softmax; ``W_o``.
  ``mla_use_nope``: ``q_pe`` and ``k_pe`` are NOT rotated;
- MLP: the first ``first_k_dense_replace`` layers dense SiLU-gated
  ``intermediate_size``; the rest ``sigmoid(W_r y)`` over the router's
  experts, chosen by score + a learned bias (it chooses, the score
  weighs), the ``num_experts_per_token`` largest, renormalised to sum 1
  (``moe_renormalize``) times ``routed_scaling_factor``; experts
  SiLU-gated of ``moe_intermediate_size``; one shared expert on the same
  input.

``held = (lo, hi)``: one device's share of expert parallelism, as in
``archs/exaone_moe.py``: the router scores all ``router_experts``, the
gates are normalised over all the chosen, this device computes the pairs
that land on experts ``lo .. hi - 1`` and the shared expert.

Departures from the published code: q, k, v, the two low-rank inputs and
beta come from one fused ``in_proj`` (a fixed permutation of random
weights); what ``config.json`` has no key for is listed in the
configuration file as ``assumed``.

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

PUBLISHED = {"first_k_dense_replace", "head_dim", "hidden_act", "hidden_size",
             "intermediate_size", "kv_lora_rank", "linear_attn_config",
             "mla_use_nope", "model_max_length", "model_type",
             "moe_intermediate_size", "moe_layer_freq", "moe_renormalize",
             "moe_router_activation_func", "num_attention_heads",
             "num_expert_group", "num_experts", "num_experts_per_token",
             "num_hidden_layers", "num_key_value_heads",
             "num_nextn_predict_layers", "num_shared_experts", "q_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
             "rope_scaling", "rope_theta", "routed_scaling_factor",
             "tie_word_embeddings", "topk_group", "use_grouped_topk",
             "v_head_dim", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "deployment", "run", "memory", "sizing_notes",
       "router_experts"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/kimi_linear.py maps no key {unknown}: a key "
                         f"it ignored would run another model under this "
                         f"name")
    want = {"model_type": "kimi_linear", "hidden_act": "silu",
            "q_lora_rank": None, "rope_scaling": None,
            "moe_router_activation_func": "sigmoid", "moe_layer_freq": 1,
            "num_expert_group": 1, "topk_group": 1,
            "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
            "num_key_value_heads": conf["num_attention_heads"]}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")
    lin = conf["linear_attn_config"]
    if set(lin) != {"full_attn_layers", "head_dim", "kda_layers",
                    "num_heads", "short_conv_kernel_size"}:
        raise ValueError(f"linear_attn_config has keys {sorted(lin)}")
    kinds = layer_kinds(conf)
    if None in kinds:
        raise ValueError("linear_attn_config names neither kda nor full "
                         f"attention for layer {kinds.index(None) + 1}")
    if not 0 < conf["num_experts"] <= _router_width(conf):
        raise ValueError("num_experts (held here) exceeds router_experts")


def layer_kinds(conf: dict) -> list:
    """``"kda"`` or ``"latent"`` for each of the first
    ``num_hidden_layers`` layers (the published lists are 1-based)."""
    lin = conf["linear_attn_config"]
    return ["kda" if i + 1 in lin["kda_layers"] else
            "latent" if i + 1 in lin["full_attn_layers"] else None
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
              mlp_dim=conf["intermediate_size"],
              moe_mlp_dim=conf["moe_intermediate_size"], max_len=max_len,
              rope_theta=float(conf["rope_theta"]), tie_embeddings=False,
              dtype=types[run["compute_dtype"]],
              attention_impl=run.get("attention", "auto"),
              norm_eps=float(conf["rms_norm_eps"]),
              layer_attn=tuple(layer_kinds(conf)),
              layer_mlp=tuple(mlp_kinds(conf)), moe_experts=router,
              moe_held=(conf["num_experts"]
                        if conf["num_experts"] < router else 0),
              moe_top_k=conf["num_experts_per_token"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=bool(conf["moe_renormalize"]),
              moe_router="sigmoid", moe_select_bias=True,
              moe_routed_scale=float(conf["routed_scaling_factor"]),
              moe_shared_dim=(conf["num_shared_experts"]
                              * conf["moe_intermediate_size"]),
              kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
              kda_conv=lin["short_conv_kernel_size"],
              kda_chunk=run.get("kda_chunk", 64),
              kda_state_dtype=types[run.get("kda_state_dtype", "float32")],
              mla_rank=conf["kv_lora_rank"],
              mla_nope_dim=conf["qk_nope_head_dim"],
              mla_rope_dim=conf["qk_rope_head_dim"],
              mla_v_dim=conf["v_head_dim"],
              mla_rope=not conf["mla_use_nope"])
    kw.update(overrides)
    return TransformerConfig(**kw)


BIAS_SCALE = 0.05


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device from the seed, one layer per
    jitted call and cast inside it, ``layer_<i>``, as
    ``archs/exaone_moe.py`` makes them.

    The program's own initialisers with PR 26's corrections (PERF.md
    section 6): each expert matrix lecun-normal BY ITSELF, norm scales 1
    + 0.1 normal, the selection bias ``BIAS_SCALE`` normal (small beside
    the scores' spread, not zero), embedding rows unit normal under an
    untied lecun-normal head.  So that the recurrence is exercised and
    not near-identity: the convolutions' weights 0.5 normal (q, k and v
    mix four positions), ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1] and ``A_log = log(uniform[1, 16])``
    (``KDAMixer``'s own: a channel forgets in one token or in a
    thousand), and beta a sigmoid of a unit-normal logit (the delta
    correction is half on)."""
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
    return conf.get("router_experts", conf["num_experts"])


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
    _, chosen = jax.lax.top_k(pick, conf["num_experts_per_token"])
    vals = jnp.take_along_axis(scores, chosen, axis=-1)
    if conf["moe_renormalize"]:
        vals = vals / vals.sum(-1, keepdims=True)
    vals = vals * float(conf["routed_scaling_factor"])
    weight = jnp.zeros_like(scores).at[
        jnp.arange(y.shape[0])[:, None], chosen].set(vals)
    return weight, chosen


def held_experts(conf: dict, p, y, held=None, nudge=None):
    """The experts ``held`` (module docstring) ALONE on ``y [T, D]``:
    this share's partial sum, the shared expert not in it.  ``(out [T,
    D], chosen)``.  ``p``'s expert matrices are those of the share."""
    lo, hi = held or (0, conf["num_experts"])
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


def mla_mixer(conf: dict, p, y, last: int | None = None):
    """The latent attention mixer on ``y [B, L, D]``, un-absorbed: keys
    and values expanded for every position and head, no rotation.  With
    ``last`` only the last ``last`` positions' outputs ``[B, last, D]``
    (their scores alone are formed)."""
    H = conf["num_attention_heads"]
    rank, nope, rope, vd = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                            conf["qk_rope_head_dim"], conf["v_head_dim"])
    b, l, _ = y.shape
    n = l if last is None else last
    q = (y[:, l - n:] @ _f32(p["q_proj"]["kernel"])).reshape(
        b, n, H, nope + rope)
    ckv = y @ _f32(p["kv_a"]["kernel"])
    c = _rmsnorm(ckv[..., :rank], p["kv_norm"]["scale"],
                 float(conf["rms_norm_eps"]))
    k_pe = ckv[..., rank:]
    kv = (c @ _f32(p["kv_b"])).reshape(b, l, H, nope + vd)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], kv[..., :nope])
         + jnp.einsum("bqhd,bkd->bhqk", q[..., nope:], k_pe)
         ) * (nope + rope) ** -0.5
    i, j = jnp.arange(l - n, l)[:, None], jnp.arange(l)[None, :]
    s = jnp.where(j <= i, s, -jnp.inf)
    a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:])
    return a.reshape(b, n, H * vd) @ _f32(p["o_proj"]["kernel"])


_MIXER_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim", "rms_norm_eps")
_MLP_KEYS = ("num_experts", "router_experts", "num_experts_per_token",
             "moe_renormalize", "routed_scaling_factor", "rms_norm_eps")


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
                      else (mla_mixer(conf, p["mla"], y), None))
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
    forward: the chunked delta rule from a zero state, the expanded
    latent attention, no cache).  Returns ``(logits [B, L, V] float32,
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


def program_attention(cfg, mla_params, y):
    """The PROGRAM's latent attention mixer alone on the EXPANDED path
    (``LatentAttention`` in a full forward) on ``y`` [B, L, D]."""
    from edl_tpu.models.transformer import LatentAttention

    pos = jnp.broadcast_to(jnp.arange(y.shape[1]), y.shape[:2])
    return _f32(jax.jit(lambda p, y: LatentAttention(cfg).apply(
        {"params": p}, y, pos))(mla_params, y.astype(cfg.dtype)))


def program_absorbed(cfg, mla_params, y, chunk: int, steps: int):
    """The PROGRAM's latent attention mixer alone THROUGH ITS CACHE on
    ``y`` [1, L, D]: all but the last ``steps`` positions in calls of
    ``chunk`` (the expanded path over the slab, the rows left in the
    cache), then ``steps`` one-token calls on the ABSORBED path (on the
    chip ``latent_append`` and ``latent_attend``) against that prefix.
    Returns those steps' outputs [steps, D] float32."""
    from edl_tpu.models.transformer import LatentAttention

    L = y.shape[1]
    mixer = LatentAttention(dataclasses.replace(
        cfg, decode=True, max_len=-(-L // 128) * 128))
    y = y[:1].astype(cfg.dtype)
    cache = jax.jit(lambda: _fresh(mixer, y[:, :1], jnp.zeros((1, 1),
                                                             jnp.int32)))()

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(p, cache, rows, start):
        out, mut = mixer.apply(
            {"params": p, "cache": cache}, rows,
            start + jnp.arange(rows.shape[1])[None],
            mutable=["cache", "intermediates"])
        return out[0, -1], mut["cache"]

    at, outs = 0, []
    while at < L:
        n = min(chunk, L - steps - at) if at < L - steps else 1
        row, cache = run(mla_params, cache, y[:, at:at + n],
                         jnp.asarray(at, jnp.int32))
        at += n
        if at > L - steps:
            outs.append(row)
    return _f32(jnp.stack(outs))


def program_cached(cfg, params, ids, chunk: int, steps: int):
    """The PROGRAM's block THROUGH ITS CACHE over ``ids`` [1, L]: a
    decode model (no engine) prefills all but the last ``steps`` tokens
    in chunks of ``chunk`` with state and latent rows carried, then
    takes the last ``steps`` tokens one at a time (on the chip
    ``kda_step``, ``latent_append`` and ``latent_attend``).  Returns
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
    return int(sum((np.asarray(c)[:, :upto] < conf["num_experts"]).sum()
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

    held, k = conf["num_experts"], conf["num_experts_per_token"]
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
    on the same ``ids``, as ``archs/granite_moe_hybrid.py`` compares,
    and prints.  Every error is the norm of (program - reference) over
    the norm of the reference's output, a token, each part fed the
    reference's own input to it:

    ``mixer_error`` [KDA layers * B * L]: every KDA mixer ALONE.
    ``attention_error`` [MLA layers * B * L]: every latent attention
    mixer alone on the EXPANDED path.
    ``absorbed_error`` [MLA layers * ``CACHE_STEPS``]: the same mixer
    THROUGH ITS CACHE (``program_absorbed``) on a seeded unit-normal
    input of ``run.absorbed_prefix`` positions: the one-token ABSORBED
    path against a latent prefix of the timed lengths, against the
    reference's un-absorbed attention of the same rows.
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
    mla = [(i, m) for i, m in ref["mixers"].items()
           if cfg.attn_kind(i) == "latent"]
    mixers = np.concatenate([_rel(program_mixer(cfg, p, y) - out, out)
                             for p, _, (y, out, _, _) in kda])
    chunk = conf["run"]["prefill_chunk"]
    states = np.concatenate([
        _rel((program_state(cfg, p, y, chunk) - last[0])[slow],
             last[0][slow], axes=(-2, -1))
        for p, slow, (y, _, _, last) in kda])
    attention = np.concatenate([
        _rel(program_attention(cfg, pp[f"layer_{i}"]["mla"], y) - out, out)
        for i, (y, out, _, _) in mla])
    n_abs = conf["run"].get("absorbed_prefix", 8192) + CACHE_STEPS
    long_y = jax.random.normal(
        jax.random.key(int(jnp.sum(ids)) % (1 << 31)),
        (1, n_abs, conf["hidden_size"]), jnp.float32)
    absorbed = []
    for i, _ in mla:
        with jax.default_matmul_precision("highest"):
            out = jax.jit(functools.partial(
                mla_mixer, conf, last=CACHE_STEPS))(
                    params[f"layer_{i}"]["mla"], long_y)[0]
        absorbed.append(_rel(program_absorbed(
            cfg, pp[f"layer_{i}"]["mla"], long_y, chunk, CACHE_STEPS) - out,
            out))
    absorbed = np.concatenate(absorbed)
    cached = program_cached(cfg, pp, ids[:1], chunk, CACHE_STEPS)
    tail = want[0, -CACHE_STEPS:]
    cache_err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(cached - tail), -1))
                           / jnp.std(tail, -1))
    pairs = held_pairs(conf, ref["chosen"])
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: KDA mixers alone, error over norm, median "
          f"{np.median(mixers):.5f} max {mixers.max():.5f} over "
          f"{mixers.size} (token, layer) pairs; latent attention alone, "
          f"expanded median {np.median(attention):.5f} max "
          f"{attention.max():.5f} over {attention.size}, absorbed against a "
          f"prefix of {n_abs - CACHE_STEPS} median {np.median(absorbed):.5f} "
          f"max {absorbed.max():.5f} over {absorbed.size}; expert layers "
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
            "absorbed_error": absorbed, "expert_error": experts,
            "routed_error": routed, "state_error": states,
            "logit_error_sigma": err, "cache_error_sigma": cache_err,
            "expert_sets_differ": differ, "held_pairs": pairs}


# -- what the algorithms need, from shapes alone ------------------------------
def kda_layers(conf: dict) -> int:
    return layer_kinds(conf).count("kda")


def latent_layers(conf: dict) -> int:
    return layer_kinds(conf).count("latent")


def sparse_layers(conf: dict) -> int:
    return mlp_kinds(conf).count("sparse")


def _kda_inner(conf: dict) -> int:
    lin = conf["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"]


def expert_params(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def expert_flops_per_assignment(conf: dict) -> float:
    """One (token, expert) pair: three matmuls, 2 FLOPs a weight."""
    return 2.0 * expert_params(conf)


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


def mla_matmul_params(conf: dict) -> int:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    rank, nope, rope, vd = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                            conf["qk_rope_head_dim"], conf["v_head_dim"])
    return (d * h * (nope + rope) + d * (rank + rope) + rank * h * (nope + vd)
            + h * vd * d)


def latent_width(conf: dict) -> int:
    """Values a latent layer caches a token: ``c | k_pe``."""
    return conf["kv_lora_rank"] + conf["qk_rope_head_dim"]


def shared_matmul_params(conf: dict) -> int:
    """Read by every token, all layers together: the mixers, the dense
    layers' MLPs, the routers and the shared experts."""
    d = conf["hidden_size"]
    dense = conf["num_hidden_layers"] - sparse_layers(conf)
    return (kda_layers(conf) * kda_matmul_params(conf)
            + latent_layers(conf) * mla_matmul_params(conf)
            + dense * 3 * d * conf["intermediate_size"]
            + sparse_layers(conf)
            * (d * _router_width(conf) + 3 * d * conf["num_shared_experts"]
               * conf["moe_intermediate_size"]))


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Of the latent layers: the only cache that grows with the context,
    one row a token a layer, keys and values the same bytes."""
    return latent_width(conf) * itemsize * latent_layers(conf)


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
    """Every parameter this device holds (``num_experts`` routed experts
    a sparse layer, the router and its bias whole, the vocabulary slice
    for the embedding and for the head)."""
    d = conf["hidden_size"]
    dense = conf["num_hidden_layers"] - sparse_layers(conf)
    return (2 * conf["vocab_size"] * d + d
            + kda_layers(conf) * kda_params(conf)
            + latent_layers(conf) * (mla_matmul_params(conf)
                                     + conf["kv_lora_rank"])
            + conf["num_hidden_layers"] * 2 * d
            + dense * 3 * d * conf["intermediate_size"]
            + sparse_layers(conf)
            * (d * _router_width(conf) + _router_width(conf)
               + 3 * d * conf["num_shared_experts"]
               * conf["moe_intermediate_size"]
               + conf["num_experts"] * expert_params(conf)))


def decode_step_min_bytes(conf: dict, experts_touched: float,
                          live_tokens: float, itemsize: int = 2,
                          live_slots: float = 0.0) -> float:
    """What one decode token step must read (and write) at least: the
    mixers', dense MLPs', routers', shared experts' and head's weights
    once, the held experts its batch touched (a layer's mean) in every
    sparse layer, the latent layers' live rows, and each of
    ``live_slots`` slots' recurrent state read once and written once.  A
    caller that knows no slot count (``moe_decode_step_roofline``'s
    reader hands none over) leaves the state out: the share it reads is
    then low, never high."""
    shared = (shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = sparse_layers(conf) * experts_touched * expert_params(conf)
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens
            + 2.0 * state_bytes_per_slot(conf, itemsize) * live_slots)


def expert_matmul_min(conf: dict, assignments: float, experts_read: float,
                      itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` the expert matmuls need for ``assignments``
    (token, expert) pairs on HELD experts that made the program read
    ``experts_read`` expert weight sets: the weights once, and each
    pair's input row read and output row written for the three
    projections."""
    d, m = conf["hidden_size"], conf["moe_intermediate_size"]
    rows = assignments * (d + 2 * m + m + d) * itemsize
    return (assignments * expert_flops_per_assignment(conf),
            experts_read * expert_params(conf) * itemsize + rows)


def kda_step_min(conf: dict, pairs: float) -> tuple[float, float]:
    """``(flops, bytes)`` of the ``kda_step`` kernel for ``pairs`` live
    (slot, token step, layer) states: the state read once and written
    once (float32); the decay, the correction's reduction, the rank-one
    update and the readout at 2 FLOPs a state element each; and the
    step's own rows as the kernel takes them in float32 (the decay, k,
    beta k and q in, v in, o out)."""
    lin = conf["linear_attn_config"]
    h, r = lin["num_heads"], lin["head_dim"]
    state = h * r * r
    rows = 4 * (4 * h * r + 2 * h * r)
    return pairs * 8.0 * state, pairs * (2.0 * 4 * state + rows)


def latent_attention_min(conf: dict, positions: float, pairs: float,
                         itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of the ``latent_append`` and ``latent_attend``
    kernels for ``positions`` live positions (summed over live slots,
    token steps and latent layers) read by ``pairs`` (slot, token step,
    layer) calls: each position's row ``c | k_pe`` read ONCE for all
    heads (keys and values are the same bytes), every head's score
    against it and its part in the value sum at 2 FLOPs a multiply-add;
    a call's own row written and its queries read and outputs written."""
    h, w = conf["num_attention_heads"], latent_width(conf)
    flops = positions * h * 2.0 * (w + conf["kv_lora_rank"])
    own = pairs * (w + 2 * h * w) * itemsize
    return flops, positions * w * itemsize + own
