"""granite-4.0-h-small (``granitemoehybrid``) for the benchmark:
configuration, weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module, as
``archs/olmoe.py`` and ``archs/exaone_moe.py`` are:
``runners/serve_ssm.py`` registers it as ``model`` and its ``reference``
as ``reference``, and ``runners/serve.py`` then calls
``transformer_config``, ``init_params`` and ``logits`` exactly as it
calls ``model.py`` and ``reference.py``.  ``block_agreement`` is what the
cell's ``correct`` also rests on: the program's own block as a whole, a
Mamba-2 layer alone and an expert layer alone against the reference on
the probe, and the host's recount of the (token, expert) pairs that
landed on the held experts.

The reference is a copy of ``tests/helpers/granite_moe_hybrid_reference.py``
(a tier-1 test holds the two equal): the forward pass in plain
``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, the
recurrence as a ``lax.scan`` over single tokens.  Its equations, with D
``hidden_size`` and ``layer_types[l]`` ``"mamba"`` or ``"attention"``:

- embedding ``x = embedding_multiplier * E[ids]``;
- every layer ``h = x + residual_multiplier * Mixer_l(RMSNorm(x))``,
  ``x' = h + residual_multiplier * (MoE(RMSNorm(h)) + Shared(RMSNorm(h)))``;
  after the last layer RMSNorm, then ``logits = (x E^T) / logits_scaling``;
- attention mixer: GQA, head D / H, no bias, NO positional embedding,
  scores ``q k^T * attention_multiplier``, causal, float32 softmax;
- Mamba-2 mixer: ``[z | xBC | dt] = y W_in``; a causal depthwise
  convolution over ``mamba_d_conv`` positions with a bias, then SiLU;
  ``dt = softplus(dt + dt_bias)``, ``a = exp(dt * A)``, ``A = -exp(A_log)``;
  ``S_t = a_t S_{t-1} + dt_t x_t (outer) B_t``; ``o_t = S_t C_t + D x_t``;
  ``RMSNorm(o * silu(z))`` over the whole inner width; ``W_out``;
- MoE: the ``num_experts_per_tok`` largest router logits, softmax over
  those; gated-SiLU experts of width ``intermediate_size``; a shared MLP
  of ``shared_intermediate_size`` on every token; nothing dropped.

``held = (lo, hi)``: one device's share of expert parallelism, as in
``archs/exaone_moe.py``: the router scores all ``router_experts``, the
gates are normalised over all the chosen, this device computes the
pairs that land on experts ``lo .. hi - 1`` and the shared MLP.

Departures from the published code: ``W_in``, the fused ``attn_qkv`` and
the separate gate / up matrices are fixed permutations of random
weights; ``time_step_limit`` is (0, inf), so dt is not clamped.  What
``config.json`` has no key for is listed in the configuration file as
``assumed``.

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
PUBLISHED = {"attention_bias", "attention_multiplier", "embedding_multiplier",
             "hidden_act", "hidden_size", "intermediate_size", "layer_types",
             "logits_scaling", "mamba_chunk_size", "mamba_conv_bias",
             "mamba_d_conv", "mamba_d_head", "mamba_d_state", "mamba_expand",
             "mamba_n_groups", "mamba_n_heads", "mamba_proj_bias",
             "max_position_embeddings", "model_type",
             "normalization_function", "num_attention_heads",
             "num_experts_per_tok", "num_hidden_layers",
             "num_key_value_heads", "num_local_experts",
             "position_embedding_type", "residual_multiplier",
             "rms_norm_eps", "rope_scaling", "rope_theta",
             "shared_intermediate_size", "tie_word_embeddings", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "deployment", "run", "memory", "sizing_notes",
       "router_experts"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/granite_moe_hybrid.py maps no key {unknown}: "
                         f"a key it ignored would run another model under "
                         f"this name")
    want = {"model_type": "granitemoehybrid", "hidden_act": "silu",
            "attention_bias": False, "position_embedding_type": "nope",
            "normalization_function": "rmsnorm", "tie_word_embeddings": True,
            "rope_scaling": None}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")
    n = conf["num_hidden_layers"]
    if len(conf["layer_types"]) < n or set(conf["layer_types"]) - {
            "mamba", "attention"}:
        raise ValueError(f"layer_types must name mamba or attention for "
                         f"each of the {n} layers")
    if (conf["mamba_n_heads"] * conf["mamba_d_head"]
            != conf["mamba_expand"] * conf["hidden_size"]):
        raise ValueError("mamba_n_heads x mamba_d_head is not mamba_expand "
                         "x hidden_size")
    if not 0 < conf["num_local_experts"] <= _router_width(conf):
        raise ValueError("num_local_experts (held here) exceeds "
                         "router_experts")


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    n = conf["num_hidden_layers"]
    types = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    router = _router_width(conf)
    kw = dict(vocab_size=conf["vocab_size"], num_layers=n,
              embed_dim=conf["hidden_size"],
              num_heads=conf["num_attention_heads"],
              num_kv_heads=conf["num_key_value_heads"],
              mlp_dim=conf["intermediate_size"],
              moe_mlp_dim=conf["intermediate_size"], max_len=max_len,
              rope_theta=float(conf["rope_theta"]), tie_embeddings=True,
              dtype=types[conf["run"]["compute_dtype"]],
              attention_impl=conf["run"].get("attention", "auto"),
              norm_eps=float(conf["rms_norm_eps"]),
              layer_attn=tuple("ssm" if t == "mamba" else "global"
                               for t in conf["layer_types"][:n]),
              rope_global=False, moe_experts=router,
              moe_held=(conf["num_local_experts"]
                        if conf["num_local_experts"] < router else 0),
              moe_top_k=conf["num_experts_per_tok"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=True,
              moe_shared_dim=conf["shared_intermediate_size"],
              ssm_heads=conf["mamba_n_heads"],
              ssm_head_dim=conf["mamba_d_head"],
              ssm_state=conf["mamba_d_state"],
              ssm_groups=conf["mamba_n_groups"],
              ssm_conv=conf["mamba_d_conv"],
              ssm_chunk=conf["mamba_chunk_size"],
              ssm_conv_bias=bool(conf["mamba_conv_bias"]),
              ssm_proj_bias=bool(conf["mamba_proj_bias"]),
              ssm_state_dtype=types[conf["run"].get("ssm_state_dtype",
                                                    "float32")],
              embed_multiplier=float(conf["embedding_multiplier"]),
              residual_multiplier=float(conf["residual_multiplier"]),
              attn_scale=float(conf["attention_multiplier"]),
              logits_scaling=float(conf["logits_scaling"]))
    kw.update(overrides)
    return TransformerConfig(**kw)


# The embedding rows' spread.  The model ties its head to the embedding
# and multiplies the embedding by 12: with unit-normal rows the residual
# stream is the input token's own row all the way up, every position's
# best logit is its own input token by tens of standard deviations, and
# neither the served-token margin nor a greedy answer could see a wrong
# mixer.  At this spread the ten layers' contributions (0.22 x 20
# branches of about unit size) are some twenty times the embedding's,
# and the best logit is the layers' doing.
EMBED_SCALE = 0.003


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device from the seed, one layer per
    jitted call and cast inside it, ``layer_<i>`` (layers that differ
    cannot be stacked), as ``archs/exaone_moe.py`` makes them.

    The program's own initialisers with PR 26's corrections (PERF.md
    section 6), without which the comparison with the reference is
    blind: each expert matrix lecun-normal BY ITSELF (``MoEMLP``'s
    initialiser counts the expert axis as a receptive field), norm
    scales 1 + 0.1 normal, embedding rows ``EMBED_SCALE`` normal.  And
    so that the recurrence is exercised and not degenerate, the usual
    Mamba-2 initialisers: ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1] and ``A_log = log(uniform[1, 16])``
    (``Mamba2Mixer``'s own), ``D`` 1 + 0.1 normal, the convolution's
    weights 0.5 normal and its bias 0.1 normal."""
    from edl_tpu.models.transformer import Block

    if not split_layers:
        raise ValueError("a stack whose layers differ has no stacked layout")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]
    D, V = cfg.embed_dim, cfg.vocab_size

    def cast(path, a, key):
        name = path[-1].key
        normal = jax.random.normal(key, a.shape, jnp.float32)
        if name in ("scale", "D"):
            a = 1.0 + 0.1 * normal
        elif name == "conv_w":
            a = 0.5 * normal
        elif name == "conv_b":
            a = 0.1 * normal
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
        k1, k2 = jax.random.split(key)
        return scaled(
            {"tok_embed": {"embedding":
                           EMBED_SCALE * jax.random.normal(k1, (V, D))},
             "final_norm": {"scale": jnp.ones((D,))}}, k2)

    keys = jax.random.split(jax.random.key(seed % (1 << 31)),
                            cfg.num_layers + 1)
    params = ends(keys[0])
    for i, k in enumerate(keys[1:]):
        params[f"layer_{i}"] = layer(k, i)
    return params


# -- the reference (tests/helpers/granite_moe_hybrid_reference.py, copied) ---
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


# -- the program's block, for the comparison ---------------------------------
def _layers(params, n):
    return [params[f"layer_{i}"] for i in range(n)]


def program_forward(cfg, params, ids):
    """The PROGRAM's block over ``ids``: ``edl_tpu``'s ``Block`` layer
    by layer, its final norm and tied head, in ``cfg``'s compute type
    (full forward, the chunked scan from a zero state, dense attention,
    no cache).  Returns ``(logits [B, L, V] float32, chosen {layer: [B,
    L, k]})``, the experts each layer's float32 router picked from the
    block's own ``mlp_norm`` output."""
    from edl_tpu.models.transformer import Block, RMSNorm

    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = jnp.take(params["tok_embed"]["embedding"], ids, axis=0).astype(
        cfg.dtype) * jnp.asarray(cfg.embed_multiplier, cfg.dtype)
    routes = {}

    # one compiled program a KIND of layer (layers of a kind compute
    # alike: the first of the kind stands for all of them)
    @functools.partial(jax.jit, static_argnums=(2,))
    def layer(p, x, like):
        (x, _), seen = Block(cfg, like).apply(
            {"params": p}, x, pos, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "mlp_norm")
        y = seen["intermediates"]["mlp_norm"]["__call__"][0]
        return x, jax.lax.top_k(_f32(y) @ _f32(p["moe"]["gate"]),
                                cfg.moe_top_k)[1]

    for i, p in enumerate(_layers(params, cfg.num_layers)):
        x, routes[i] = layer(p, x, cfg.layer_attn.index(cfg.attn_kind(i)))
    x = RMSNorm(cfg.dtype, cfg.norm_eps).apply(
        {"params": params["final_norm"]}, x)
    out = x @ params["tok_embed"]["embedding"].T.astype(cfg.dtype)
    return _f32(out) / cfg.logits_scaling, routes


def program_experts(cfg, moe_params, y, shared: bool = True):
    """The PROGRAM's expert layer alone (``ops/moe.py``'s ``MoEMLP`` as
    ``Block`` builds it: router, held experts, shared MLP) on ``y``
    [B, L, D], in ``cfg``'s compute type; without ``shared`` the held
    experts' partial sum alone."""
    from edl_tpu.ops.moe import MoEMLP

    layer = MoEMLP(num_experts=cfg.moe_experts, mlp_dim=cfg.expert_dim,
                   top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity,
                   dtype=cfg.dtype, gated=cfg.moe_gated,
                   norm_topk=cfg.moe_norm_topk, router=cfg.moe_router,
                   shared_dim=cfg.moe_shared_dim if shared else 0,
                   held=cfg.moe_held)
    (out, _), _ = jax.jit(lambda p, y: layer.apply(
        {"params": p}, y, mutable=["intermediates"]))(
            moe_params, y.astype(cfg.dtype))
    return _f32(out)


def program_mixer(cfg, ssm_params, y):
    """The PROGRAM's Mamba-2 mixer alone (``Mamba2Mixer``: projections,
    convolution, the chunked scan, gate and norm) on ``y`` [B, L, D]."""
    from edl_tpu.models.transformer import Mamba2Mixer

    return _f32(jax.jit(lambda p, y: Mamba2Mixer(cfg).apply({"params": p}, y))(
        ssm_params, y.astype(cfg.dtype)))


def program_state(cfg, ssm_params, y, chunk: int):
    """The PROGRAM's Mamba-2 mixer alone THROUGH ITS CACHE on ``y`` [1,
    L, D]: a decode-mode ``Mamba2Mixer`` takes the first ``chunk``
    positions in one call (the chunked scan, the state left in the
    cache) and every later position one token at a time from the cached
    state (on the chip the ``ssm_step`` kernel), as a slot of the engine
    does.  Returns the recurrent state the cache holds at the end, [H,
    P, N] float32: hundreds of one-token updates, each kept in
    ``cfg.ssm_state_dtype``."""
    return _state_program(cfg, chunk)(ssm_params, y[:1].astype(cfg.dtype))


def _state_program(cfg, chunk: int):
    from edl_tpu.models.transformer import Mamba2Mixer

    mixer = Mamba2Mixer(dataclasses.replace(cfg, decode=True))

    @jax.jit
    def run(p, y):
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(mixer.init, jax.random.key(0), y[:, :1])["cache"])
        _, mut = mixer.apply({"params": p, "cache": cache}, y[:, :chunk],
                             mutable=["cache"])

        def one(cache, yt):
            _, mut = mixer.apply({"params": p, "cache": cache}, yt[:, None],
                                 mutable=["cache"])
            return mut["cache"], None

        cache, _ = jax.lax.scan(one, mut["cache"],
                                jnp.moveaxis(y[:, chunk:], 1, 0))
        return _f32(cache["ssm_state"][0])

    return run


def program_attention(cfg, layer_params, x, layer: int):
    """The PROGRAM's attention mixer alone: ``Block`` ``layer`` on ``x``
    [B, L, D] (the layer's input BEFORE its norm), and of what it
    computes the output of ``attn_out``: norm, projections, the scores
    under ``attention_multiplier`` with no rotation, the output
    matrix."""
    from edl_tpu.models.transformer import Block

    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])

    @jax.jit
    def run(p, x):
        _, seen = Block(cfg, layer).apply(
            {"params": p}, x, pos, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "attn_out")
        return seen["intermediates"]["attn_out"]["__call__"][0]

    return _f32(run(layer_params, x.astype(cfg.dtype)))


def program_cached(cfg, params, ids, chunk: int, steps: int):
    """The PROGRAM's block THROUGH ITS CACHE over ``ids`` [1, L]: a
    decode model (``TransformerLM`` with ``decode=True``, no engine)
    prefills all but the last ``steps`` tokens in chunks of ``chunk``
    with the state carried from chunk to chunk (the chunked scan from
    the cached state), then takes the last ``steps`` tokens one at a
    time (the one-token recurrence: on the chip the ``ssm_step`` and
    ``decode_attend`` kernels).  Returns those steps' logits [steps, V]
    float32: what the engine's programs compute, at the level of
    logits, which served tokens alone cannot show."""
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
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=start + jnp.arange(tokens.shape[1])[None],
            mutable=["cache"])
        return logits[0, -1], mut["cache"]

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
    experts held here, summed over the layers."""
    import numpy as np
    return int(sum((np.asarray(c)[:, :upto] < conf["num_local_experts"]).sum()
                   for c in chosen.values()))


def _rel(diff, want, axes=-1):
    import numpy as np
    return np.asarray(jnp.linalg.norm(diff, axis=axes)
                      / jnp.maximum(jnp.linalg.norm(want, axis=axes), 1e-30)
                      ).reshape(-1)


def slow_heads(ssm_params):
    """The tenth of a Mamba-2 mixer's heads (one at least) whose state
    decays slowest: the smallest ``softplus(dt_bias) * exp(A_log)``, the
    decay rate a step at a zero projection.  At the published
    initialisers they remember 100 steps and more."""
    rate = jax.nn.softplus(_f32(ssm_params["dt_bias"])) * jnp.exp(
        _f32(ssm_params["A_log"]))
    return jnp.argsort(rate)[:max(1, rate.shape[0] // 10)]


CACHE_STEPS = 16


def block_agreement(conf: dict, params, ids, ref: dict, *, cfg=None,
                    program_params=None, tag: str = "") -> dict:
    """The program's block (``cfg`` and ``program_params`` let a
    deliberately wrong variant stand in) against ``reference``'s ``ref``
    on the same ``ids``, as ``archs/exaone_moe.py`` compares, and prints:

    ``mixer_error`` [mamba layers * B * L]: every Mamba-2 mixer ALONE,
    fed the reference's own input to that layer: the norm of (program -
    reference) over the norm of the reference's output, a token.
    ``attention_error`` [attention layers * B * L]: the same for every
    attention mixer (fed the reference's input to that LAYER: its norm
    is the program's too).
    ``expert_error`` [layers * B * L]: the same for every expert layer
    (router, held experts, shared MLP).  The median over tokens and
    layers, because a token whose 10th and 11th expert swap on the
    rounded input is far out and honest.
    ``routed_error`` [layers * B * L]: the same with the shared MLP
    taken out of both sides, the held experts' partial sum alone: the
    shared MLP is whole on every token and five small gates' worth of
    experts beside it are a fortieth of the layer's output, so the
    layer's error hardly sees the experts' own precision
    (``archs/exaone_moe.py``'s ``held_expert_error``).
    ``state_error`` [mamba layers * slow heads]: every Mamba-2 mixer
    alone THROUGH ITS CACHE (``program_state``: one chunk, then every
    later position a one-token update of the cached state), fed the
    reference's own input: the norm of (the state the cache holds at
    the end - the reference recurrence's) over the reference's, a head,
    for each layer's ``slow_heads``: the tenth of its heads with the
    longest memory, where what hundreds of updates round away stays.
    ``logit_error_sigma`` [B * L]: the whole block (``program_forward``)
    at the level of logits: at every position the root mean square over
    the vocabulary of (program - reference), in standard deviations of
    the reference's logits there.
    ``cache_error_sigma`` [``CACHE_STEPS``]: the same measure for the
    block THROUGH ITS CACHE (``program_cached``: chunked prefill with
    state carried, then one-token steps) at the last positions of the
    probe, against the reference's one full pass.
    ``expert_sets_differ``: the share of (token, layer) pairs whose
    chosen set in the whole block differs from the reference's.
    ``held_pairs``: the host's recount (``held_pairs``)."""
    import numpy as np

    cfg = cfg or transformer_config(conf, max_len=ids.shape[1], remat=False,
                                    attention_impl="dense")
    program_params = params if program_params is None else program_params
    own, picked = program_forward(cfg, program_params, ids)
    want = ref["logits"]
    differ = float(np.mean([
        np.asarray((jnp.sort(picked[i], -1) != jnp.sort(c, -1)).any(-1))
        for i, c in ref["chosen"].items()]))
    err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(own - want), -1))
                     / jnp.std(want, -1)).reshape(-1)
    experts = np.concatenate([
        _rel(program_experts(cfg, program_params[f"layer_{i}"]["moe"], y)
             - out, out) for i, (y, out, _) in ref["experts"].items()])
    routed = np.concatenate([
        _rel(program_experts(cfg, program_params[f"layer_{i}"]["moe"], y,
                             shared=False) - part, part)
        for i, (y, _, part) in ref["experts"].items()])
    mamba = [(program_params[f"layer_{i}"]["ssm"],
              slow_heads(params[f"layer_{i}"]["ssm"]), m)
             for i, m in ref["mixers"].items() if cfg.attn_kind(i) == "ssm"]
    mixers = np.concatenate([_rel(program_mixer(cfg, p, y) - out, out)
                             for p, _, (y, out, _, _) in mamba])
    chunk = conf["run"]["prefill_chunk"]
    states = np.concatenate([
        _rel((program_state(cfg, p, y, chunk) - last[0])[slow],
             last[0][slow], axes=(-2, -1))
        for p, slow, (y, _, _, last) in mamba])
    attention = np.concatenate([
        _rel(program_attention(cfg, program_params[f"layer_{i}"], x, i)
             - out, out) for i, (_, out, x, _) in ref["mixers"].items()
        if cfg.attn_kind(i) != "ssm"])
    cached = program_cached(cfg, program_params, ids[:1], chunk, CACHE_STEPS)
    tail = want[0, -CACHE_STEPS:]
    cache_err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(cached - tail), -1))
                           / jnp.std(tail, -1))
    pairs = held_pairs(conf, ref["chosen"])
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: Mamba-2 mixers alone, error over norm, median "
          f"{np.median(mixers):.5f} max {mixers.max():.5f} over "
          f"{mixers.size} (token, layer) pairs; attention mixers alone "
          f"median {np.median(attention):.5f} max {attention.max():.5f} over "
          f"{attention.size}; expert layers alone median "
          f"{np.median(experts):.5f} mean {experts.mean():.5f} over "
          f"{experts.size}, their held experts alone median "
          f"{np.median(routed):.5f}; the state after one chunk of {chunk} "
          f"and {max(0, ids.shape[1] - chunk)} one-token updates, error "
          f"over norm a slow head, median {np.median(states):.5f} max "
          f"{states.max():.5f} over {states.size}; logits, median "
          f"{np.median(err):.5f} mean "
          f"{err.mean():.5f} max {err.max():.5f} sigma over {err.size} "
          f"positions; through the cache (chunks of "
          f"{conf['run']['prefill_chunk']}, then {CACHE_STEPS} one-token "
          f"steps) median {np.median(cache_err):.5f} max "
          f"{cache_err.max():.5f} sigma; expert sets differ in {100 * differ:.3f}% of the "
          f"(token, layer) pairs; {pairs} pairs on held experts", flush=True)
    return {"mixer_error": mixers, "attention_error": attention,
            "expert_error": experts, "routed_error": routed,
            "state_error": states,
            "logit_error_sigma": err, "cache_error_sigma": cache_err,
            "expert_sets_differ": differ,
            "held_pairs": pairs}


# -- what the algorithms need, from shapes alone ------------------------------
def _layer_kinds(conf: dict):
    return conf["layer_types"][:conf["num_hidden_layers"]]


def mamba_layers(conf: dict) -> int:
    return _layer_kinds(conf).count("mamba")


def attention_layers(conf: dict) -> int:
    return _layer_kinds(conf).count("attention")


def sparse_layers(conf: dict) -> int:
    """Every layer has the expert block."""
    return conf["num_hidden_layers"]


def _inner(conf: dict) -> int:
    return conf["mamba_n_heads"] * conf["mamba_d_head"]


def _conv_dim(conf: dict) -> int:
    return _inner(conf) + 2 * conf["mamba_n_groups"] * conf["mamba_d_state"]


def expert_params(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_flops_per_assignment(conf: dict) -> float:
    """One (token, expert) pair: three matmuls, 2 FLOPs a weight."""
    return 2.0 * expert_params(conf)


def mamba_matmul_params(conf: dict) -> int:
    d = conf["hidden_size"]
    return (d * (_inner(conf) + _conv_dim(conf) + conf["mamba_n_heads"])
            + _inner(conf) * d)


def mamba_params(conf: dict) -> int:
    """A Mamba-2 mixer whole: the projections, the convolution and its
    bias, dt_bias, A_log and D, the gated norm's scale."""
    return (mamba_matmul_params(conf)
            + _conv_dim(conf) * (conf["mamba_d_conv"]
                                 + bool(conf["mamba_conv_bias"]))
            + 3 * conf["mamba_n_heads"] + _inner(conf))


def attention_params(conf: dict) -> int:
    d = conf["hidden_size"]
    dh = d // conf["num_attention_heads"]
    return 2 * d * d + 2 * d * conf["num_key_value_heads"] * dh


def shared_matmul_params(conf: dict) -> int:
    """Read by every token, all layers together: the mixers, the
    routers and the shared MLPs."""
    d = conf["hidden_size"]
    return (mamba_layers(conf) * mamba_matmul_params(conf)
            + attention_layers(conf) * attention_params(conf)
            + conf["num_hidden_layers"]
            * (d * _router_width(conf)
               + 3 * d * conf["shared_intermediate_size"]))


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Of the attention layers: the only cache that grows with the
    context."""
    dh = conf["hidden_size"] // conf["num_attention_heads"]
    return (2 * conf["num_key_value_heads"] * dh * itemsize
            * attention_layers(conf))


def state_bytes_per_slot(conf: dict, itemsize: int = 2,
                         state_itemsize: int = 4) -> int:
    """A slot's recurrent state in all the Mamba-2 layers, whatever the
    context's length: S [H, P, N] float32 and the convolution's last
    ``mamba_d_conv - 1`` inputs."""
    return mamba_layers(conf) * (
        _inner(conf) * conf["mamba_d_state"] * state_itemsize
        + (conf["mamba_d_conv"] - 1) * _conv_dim(conf) * itemsize)


def param_count(conf: dict) -> int:
    """Every parameter this device holds (``num_local_experts`` routed
    experts a layer, the router whole, the vocabulary slice; the head
    is the embedding)."""
    d = conf["hidden_size"]
    return (conf["vocab_size"] * d + d
            + mamba_layers(conf) * mamba_params(conf)
            + attention_layers(conf) * attention_params(conf)
            + conf["num_hidden_layers"]
            * (d * _router_width(conf)
               + 3 * d * conf["shared_intermediate_size"]
               + conf["num_local_experts"] * expert_params(conf) + 2 * d))


def decode_step_min_bytes(conf: dict, experts_touched: float,
                          live_tokens: float, itemsize: int = 2,
                          live_slots: float = 0.0) -> float:
    """What one decode token step must read (and write) at least: the
    mixers', routers', shared MLPs' and head's weights once, the held
    experts its batch touched (a layer's mean) in every layer, the
    attention layers' live keys and values, and each of ``live_slots``
    slots' recurrent state read once and written once.  A caller that
    knows no slot count (``moe_decode_step_roofline``'s reader hands
    none over) leaves the state out: the share it reads is then low,
    never high."""
    shared = (shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = sparse_layers(conf) * experts_touched * expert_params(conf)
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens
            + 2.0 * state_bytes_per_slot(conf, itemsize) * live_slots)


def expert_matmul_min(conf: dict, assignments: float, experts_read: float,
                      itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` the grouped matmuls need for ``assignments``
    (token, expert) pairs on HELD experts that made the program read
    ``experts_read`` expert weight sets (summed over layers and
    programs): the weights once, and each pair's input row read and
    output row written for the three projections."""
    d, m = conf["hidden_size"], conf["intermediate_size"]
    rows = assignments * (d + 2 * m + m + d) * itemsize
    return (assignments * expert_flops_per_assignment(conf),
            experts_read * expert_params(conf) * itemsize + rows)


def ssm_step_min(conf: dict, pairs: float, itemsize: int = 2
                 ) -> tuple[float, float]:
    """``(flops, bytes)`` of the ``ssm_step`` kernel for ``pairs`` live
    (slot, token step, layer) states: the state read once and written
    once (float32), the update (decay, outer product, add) and the
    readout at 2 FLOPs a multiply-add each, and the step's own rows as
    the kernel takes them in float32: ``dt * x`` and the decay in, B and
    C in, y out."""
    h, p, n = conf["mamba_n_heads"], conf["mamba_d_head"], conf["mamba_d_state"]
    state = h * p * n
    rows = 4 * (2 * h * p + h + 2 * conf["mamba_n_groups"] * n)
    return pairs * 6.0 * state, pairs * (2.0 * 4 * state + rows)
