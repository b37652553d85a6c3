"""openPangu-Ultra-MoE-718B (``pangu_ultra_moe``) for the benchmark:
configuration, weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module, as
``archs/kimi_linear.py`` is: ``runners/serve_mla.py`` registers it as
``model`` and its ``reference`` as ``reference``, and ``runners/serve.py``
then calls ``transformer_config``, ``init_params`` and ``logits`` exactly
as it calls ``model.py`` and ``reference.py``.  ``block_agreement`` is
what the cell's ``correct`` also rests on.

The reference is the forward pass in plain ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``: the latent attention
un-absorbed, no cache, no kernels, no chunks, no sort.  Nothing of
``edl_tpu`` is in it (the tier-1 tests in ``tests/test_pangu_ultra_moe.py``
hold the program to it at a toy size).  Its equations, with D
``hidden_size``, every RMSNorm with ``rms_norm_eps``, no bias anywhere:

- block, ``sandwich_norm``: ``x = x + N2(Attn(N1(x)))``; ``x = x +
  N4(MLP(N3(x)))`` (``input_layernorm``, ``post_attention_layernorm``,
  ``pre_mlp_layernorm``, ``post_mlp_layernorm``); a final RMSNorm and an
  untied head;
- attention, every layer (H heads): ``q = W_qb RMSNorm(W_qa y)`` ->
  ``[H, nope + rope]`` = ``q_nope | q_pe``; ``W_kva y`` -> ``c' | k_pe``;
  ``c = RMSNorm(c')``; ``W_kvb c`` -> ``[H, nope + v]`` = ``k_nope | v``;
  ``q_pe`` and the one shared ``k_pe`` rotated by position
  (``rope_theta``, no scaling); scores ``(q_nope . k_nope + q_pe . k_pe)
  / sqrt(nope + rope)``, causal, float32 softmax; ``W_o``;
- MLP: layers ``< first_k_dense_replace`` SiLU-gated of
  ``intermediate_size``; the rest ``s = sigmoid(W_r y)`` over the
  router's experts, the ``num_experts_per_tok`` largest chosen by score,
  their scores divided by their sum (``norm_topk_prob``) and multiplied
  by ``routed_scaling_factor``; experts SiLU-gated of
  ``moe_intermediate_size``; one shared expert on the same input.

ASSUMED, because ``config.json`` has no key for them (the configuration
file lists them under ``assumed``): the router scores with a SIGMOID and
chooses among ALL experts in one group, the convention of the family
whose other keys the file shares value for value; there is NO selection
bias (the file has no ``topk_method``, and a zero bias is the same
function).

``held = (lo, hi)``: one device's share of expert parallelism, as in
``archs/kimi_linear.py``: the router scores all ``router_experts``, the
gates are normalised over all the chosen, this device computes the pairs
that land on experts ``lo .. hi - 1`` and the shared expert; what absent
experts would add is left out in program and reference alike.

Departures from the published code: the rotation turns interleaved pairs
``(x[2i], x[2i + 1])`` where the published code turns half-split pairs
``(x[i], x[i + d / 2])`` (a fixed permutation of the random ``q_pe`` /
``k_pe`` columns, as ``benchmarks/reference.py`` notes for the dense
model); an expert's gate and up matrices are separate; the
multi-token-prediction layer is not served (``num_nextn_predict_layers``
0 here: it lies on a further pipeline stage).

So that the float32 reference fits beside a 14 GB engine it casts ONE
matrix at a time (a dense MLP matrix is 566 MB in float32, ``W_o`` 503,
one expert 189), forms attention in groups of heads, and keeps what the
comparisons need of its activations on the host.

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

PUBLISHED = {"attention_bias", "first_k_dense_replace", "hidden_act",
             "hidden_size", "intermediate_size", "kv_lora_rank",
             "max_position_embeddings", "model_type", "moe_intermediate_size",
             "n_routed_experts", "n_shared_experts", "norm_topk_prob",
             "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
             "num_key_value_heads", "num_nextn_predict_layers", "q_lora_rank",
             "qk_nope_head_dim", "qk_rope_head_dim", "rms_norm_eps",
             "rope_theta", "routed_scaling_factor", "sandwich_norm",
             "tie_word_embeddings", "v_head_dim", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "deployment", "run", "memory", "sizing_notes",
       "router_experts"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/pangu_ultra_moe.py maps no key {unknown}: a "
                         f"key it ignored would run another model under this "
                         f"name")
    want = {"model_type": "pangu_ultra_moe", "hidden_act": "silu",
            "attention_bias": False, "tie_word_embeddings": False,
            "num_nextn_predict_layers": 0,
            "num_key_value_heads": conf["num_attention_heads"]}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")
    if not conf["q_lora_rank"] or conf["q_lora_rank"] < 1:
        raise ValueError("q_lora_rank: this model's query is low-rank")
    if not 0 < conf["n_routed_experts"] <= _router_width(conf):
        raise ValueError("n_routed_experts (held here) exceeds "
                         "router_experts")
    if not 0 <= conf["first_k_dense_replace"] <= conf["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace exceeds num_hidden_layers")


def mlp_kinds(conf: dict) -> list:
    return ["dense" if i < conf["first_k_dense_replace"] else "sparse"
            for i in range(conf["num_hidden_layers"])]


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    if not {"mla_q_rank", "post_norms"} <= fields:
        # a program from before this architecture: no result, at once
        raise SystemExit("[bench] this program's TransformerConfig has no "
                         "low-rank query or sandwich norms: it cannot run "
                         "pangu_ultra_moe")
    types = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    router = _router_width(conf)
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
              layer_attn=("latent",) * conf["num_hidden_layers"],
              layer_mlp=tuple(mlp_kinds(conf)), moe_experts=router,
              moe_held=(conf["n_routed_experts"]
                        if conf["n_routed_experts"] < router else 0),
              moe_top_k=conf["num_experts_per_tok"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=bool(conf["norm_topk_prob"]),
              moe_router="sigmoid", moe_select_bias=False,
              moe_routed_scale=float(conf["routed_scaling_factor"]),
              moe_shared_dim=(conf["n_shared_experts"]
                              * conf["moe_intermediate_size"]),
              mla_rank=conf["kv_lora_rank"],
              mla_nope_dim=conf["qk_nope_head_dim"],
              mla_rope_dim=conf["qk_rope_head_dim"],
              mla_v_dim=conf["v_head_dim"], mla_rope=True,
              mla_q_rank=conf["q_lora_rank"],
              post_norms=bool(conf["sandwich_norm"]))
    kw.update(overrides)
    return TransformerConfig(**kw)


# The routers' weights come from this key whatever ``--seed`` is: which
# of a token's eight experts are held here is then the inputs' doing
# alone (PERF.md section 7, PR 40 a, PR 37 j: the seed's router moved the
# held pairs by a quarter and p50 by 5% in the cells that draw it)
ROUTER_KEY = 20261001


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device, one layer per jitted call and
    cast inside it, ``layer_<i>``, as ``archs/kimi_linear.py`` makes
    them: the program's own initialisers with PR 26's corrections (each
    expert matrix lecun-normal BY ITSELF, norm scales 1 + 0.1 normal,
    embedding rows unit normal under an untied lecun-normal head).  Every
    leaf is drawn from ``seed`` but the routers' ``gate``, which come
    from ``ROUTER_KEY`` and the layer's number."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block

    if not split_layers:
        raise ValueError("a stack whose layers differ has no stacked layout")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]
    D, V = cfg.embed_dim, cfg.vocab_size

    def cast(path, a, key):
        name = path[-1].key
        if name == "scale":
            a = 1.0 + 0.1 * jax.random.normal(key, a.shape, jnp.float32)
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
        if "moe" in p:
            p["moe"]["gate"] = nn.initializers.lecun_normal()(
                jax.random.fold_in(jax.random.key(ROUTER_KEY), i),
                p["moe"]["gate"].shape, jnp.float32)
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
    return jnp.asarray(x).astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(scale)


@jax.jit
def _mm(x, w):
    """``x @ w`` with ONE matrix cast to float32."""
    with jax.default_matmul_precision("highest"):
        return x @ _f32(w)


def _gated(y, w_gate, w_in, w_out):
    return _mm(jax.nn.silu(_mm(y, w_gate)) * _mm(y, w_in), w_out)


def _router_width(conf: dict) -> int:
    return conf.get("router_experts", conf["n_routed_experts"])


def route(y, gate, conf, nudge=None):
    """``(weight [T, E], chosen [T, k])``: every token's gates as a
    dense matrix over ALL the router's experts, and the experts it
    chose: the k largest sigmoid scores, renormalised and scaled.
    ``nudge`` [T, E] is added to what CHOOSES, never to what weighs: how
    a caller has a near-tie between two experts resolved the other way
    for one token (``tie_aware_shortfall``)."""
    scores = jax.nn.sigmoid(y @ _f32(gate))                   # [T, E]
    pick = scores if nudge is None else scores + nudge
    _, chosen = jax.lax.top_k(pick, conf["num_experts_per_tok"])
    vals = jnp.take_along_axis(scores, chosen, axis=-1)
    if conf["norm_topk_prob"]:
        vals = vals / vals.sum(-1, keepdims=True)
    vals = vals * float(conf["routed_scaling_factor"])
    weight = jnp.zeros_like(scores).at[
        jnp.arange(y.shape[0])[:, None], chosen].set(vals)
    return weight, chosen


@functools.partial(jax.jit, static_argnames=("conf", "held"))
def _held_experts(y, p, nudge, *, conf, held):
    conf = dict(conf)
    lo, hi = held or (0, conf["n_routed_experts"])
    with jax.default_matmul_precision("highest"):
        weight, chosen = route(y, p["gate"], conf, nudge)

        def expert(acc, e):
            w_gate, w_in, w_out, w = e
            out = (jax.nn.silu(y @ _f32(w_gate)) * (y @ _f32(w_in))
                   ) @ _f32(w_out)
            return acc + out * w[:, None], None

        out, _ = jax.lax.scan(expert, jnp.zeros_like(y),
                              (p["w_gate"], p["w_in"], p["w_out"],
                               weight[:, lo:hi].T))
    return out, chosen


_MLP_KEYS = ("n_routed_experts", "router_experts", "num_experts_per_tok",
             "norm_topk_prob", "routed_scaling_factor")


def _frozen(conf: dict, keys):
    """The configuration as a hashable static argument."""
    return tuple((k, conf[k]) for k in keys if k in conf)


def held_experts(conf: dict, p, y, held=None, nudge=None):
    """The experts ``held`` (module docstring) ALONE on ``y [T, D]``:
    this share's partial sum, the shared expert not in it, one expert's
    matrices cast at a time.  ``(out [T, D], chosen)``.  ``p``'s expert
    matrices are those of the share."""
    routed = {k: p[k] for k in ("gate", "w_gate", "w_in", "w_out")}
    return _held_experts(y, routed, nudge, conf=_frozen(conf, _MLP_KEYS),
                         held=held)


def moe_mlp(conf: dict, p, y, held=None, nudge=None):
    """The expert block on ``y [T, D]``: ``held_experts`` and the shared
    expert.  ``(out [T, D], chosen, the held experts' partial sum)``."""
    routed, chosen = held_experts(conf, p, y, held, nudge)
    shared = _gated(y, p["shared_gate"]["kernel"], p["shared_in"]["kernel"],
                    p["shared_out"]["kernel"])
    return routed + shared, chosen, routed


def rotate(x, positions, theta: float):
    """The rotary embedding over the last dim of ``x [..., L, d]`` at
    ``positions [L]``: interleaved pairs (module docstring), angles and
    arithmetic in float32."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * freqs       # [L, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


# float32 values one group of heads may hold of a position's scores or of
# its expanded keys and values: 256 MiB
_GROUP_VALUES = 1 << 26


def _head_group(H: int, L: int, n: int, kv: int) -> int:
    hg = H
    while hg > 1 and hg % 2 == 0 and hg * L * max(n, kv) > _GROUP_VALUES:
        hg //= 2
    return hg


@functools.partial(jax.jit, static_argnames=("conf", "hg"))
def _attend(q, c, k_pe, w_kvb, *, conf, hg):
    """Un-absorbed causal attention of the last ``n`` positions' queries
    ``q [n, H, nope + rope]`` over ``c [L, rank]`` and the rotated
    ``k_pe [L, rope]``, ``hg`` heads at a time: ``[n, H * v]``."""
    conf = dict(conf)
    nope, rope_d, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                        conf["v_head_dim"])
    n, H, _ = q.shape
    L, rank = c.shape
    w = _f32(w_kvb).reshape(rank, H // hg, hg, nope + vd)
    i, j = jnp.arange(L - n, L)[:, None], jnp.arange(L)[None, :]

    def group(args):
        qg, wg = args                   # [n, hg, nope + rope], [rank, hg, .]
        kv = jnp.einsum("lc,chd->lhd", c, wg)
        s = (jnp.einsum("qhd,khd->hqk", qg[..., :nope], kv[..., :nope])
             + jnp.einsum("qhd,kd->hqk", qg[..., nope:], k_pe)
             ) * (nope + rope_d) ** -0.5
        s = jnp.where(j <= i, s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1),
                          kv[..., nope:])

    with jax.default_matmul_precision("highest"):
        out = jax.lax.map(group, (
            jnp.moveaxis(q.reshape(n, H // hg, hg, nope + rope_d), 1, 0),
            jnp.moveaxis(w, 1, 0)))                  # [groups, n, hg, v]
    return jnp.moveaxis(out, 0, 1).reshape(n, H * vd)


_ATTN_KEYS = ("qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim")


def mla_mixer(conf: dict, p, y, last: int | None = None, start: int = 0):
    """The latent attention mixer on ``y [B, L, D]`` (the normed input
    of positions ``start .. start + L - 1``), un-absorbed: keys and
    values expanded for every position and head, ``q_pe`` and ``k_pe``
    rotated.  With ``last`` only the last ``last`` positions' outputs
    ``[B, last, D]`` (their scores alone are formed)."""
    H = conf["num_attention_heads"]
    rank, nope, rope_d, vd = (conf["kv_lora_rank"], conf["qk_nope_head_dim"],
                              conf["qk_rope_head_dim"], conf["v_head_dim"])
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    b, l, _ = y.shape
    n = l if last is None else last
    pos = start + jnp.arange(l)
    hg = _head_group(H, l, n, nope + vd)
    outs = []
    for row in y:                                   # [L, D]
        q = _mm(_rmsnorm(_mm(row[l - n:], p["q_a"]["kernel"]),
                         p["q_norm"]["scale"], eps),
                p["q_b"]["kernel"]).reshape(n, H, nope + rope_d)
        q = jnp.concatenate(
            [q[..., :nope],
             jnp.moveaxis(rotate(jnp.moveaxis(q[..., nope:], 1, 0),
                                 pos[l - n:], theta), 0, 1)], axis=-1)
        ckv = _mm(row, p["kv_a"]["kernel"])
        c = _rmsnorm(ckv[:, :rank], p["kv_norm"]["scale"], eps)
        k_pe = rotate(ckv[:, rank:], pos, theta)
        a = _attend(q, c, k_pe, p["kv_b"], conf=_frozen(conf, _ATTN_KEYS),
                    hg=hg)
        outs.append(_mm(a, p["o_proj"]["kernel"]))
    return jnp.stack(outs)


def _host(x):
    return np.asarray(x)


def forward(conf: dict, params, ids, held=None, nudge=None):
    """``(hidden [B, L, D] before the last norm, chosen {sparse layer:
    [B, L, k]}, experts {sparse layer: (input, output, the held experts'
    part of the output)}, mixers {layer: (input, output)})``, the last
    two ON THE HOST (numpy).  ``nudge`` {layer: [B, L, E]} as ``route``
    takes it."""
    eps = float(conf["rms_norm_eps"])
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    b, l, d = x.shape
    routes, experts, mixers = {}, {}, {}
    for i in range(conf["num_hidden_layers"]):
        p = params[f"layer_{i}"]
        y = _rmsnorm(x, p["attn_norm"]["scale"], eps)
        out = mla_mixer(conf, p["mla"], y)
        mixers[i] = (_host(y), _host(out))
        x = x + _rmsnorm(out, p["attn_post_norm"]["scale"], eps)
        y = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
        if "moe" not in p:                      # a leading dense layer
            out = _gated(y, p["mlp_gate"]["kernel"], p["mlp_in"]["kernel"],
                         p["mlp_out"]["kernel"])
        else:
            nd = (nudge or {}).get(i)
            out, chosen, routed = moe_mlp(
                conf, p["moe"], y.reshape(b * l, d), held,
                None if nd is None else nd.reshape(b * l, -1))
            out = out.reshape(b, l, d)
            routes[i] = chosen.reshape(b, l, -1)
            experts[i] = (_host(y), _host(out), _host(routed.reshape(b, l, d)))
        x = x + _rmsnorm(out, p["mlp_post_norm"]["scale"], eps)
    return x, routes, experts, mixers


def reference(conf: dict, params, ids, held=None, nudge=None) -> dict:
    """The full forward pass: ``logits`` [B, L, V] float32, ``chosen``,
    ``experts`` and ``mixers`` (``forward``)."""
    x, chosen, experts, mixers = forward(conf, params, ids, held, nudge)
    x = _rmsnorm(x, params["final_norm"]["scale"],
                 float(conf["rms_norm_eps"]))
    return {"logits": _mm(x, params["lm_head"]["kernel"]),
            "chosen": chosen, "experts": experts, "mixers": mixers}


def logits(conf: dict, params, ids, held=None):
    """[B, L, V] float32 logits of the full forward pass."""
    return reference(conf, params, ids, held)["logits"]


# -- the program's block, for the comparison ---------------------------------
def program_forward(cfg, params, ids):
    """The PROGRAM's block over ``ids``: ``edl_tpu``'s ``Block`` layer by
    layer, its final norm and head, in ``cfg``'s compute type (full
    forward: the expanded latent attention, no cache).  Returns
    ``(logits [B, L, V] float32, chosen {sparse layer: [B, L, k]})``,
    the experts each layer's float32 router picked from the block's own
    ``mlp_norm`` output."""
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
        pick = jax.nn.sigmoid(_f32(y) @ _f32(p["moe"]["gate"]))
        return x, jax.lax.top_k(pick, cfg.moe_top_k)[1]

    kinds = [cfg.mlp_kind(i) for i in range(cfg.num_layers)]
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
    if not shared:
        moe_params = {k: v for k, v in moe_params.items()
                      if not k.startswith("shared_")}
    (out, _), _ = jax.jit(lambda p, y: layer.apply(
        {"params": p}, y, mutable=["intermediates"]))(
            moe_params, jnp.asarray(y).astype(cfg.dtype))
    return _f32(out)


def _fresh(module, *args):
    return jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(module.init, jax.random.key(0), *args)["cache"])


def program_attention(cfg, mla_params, y):
    """The PROGRAM's latent attention mixer alone on the EXPANDED path
    (``LatentAttention`` in a full forward) on ``y`` [B, L, D]."""
    from edl_tpu.models.transformer import LatentAttention

    pos = jnp.broadcast_to(jnp.arange(y.shape[1]), y.shape[:2])
    return _f32(jax.jit(lambda p, y: LatentAttention(cfg).apply(
        {"params": p}, y, pos))(mla_params, jnp.asarray(y).astype(cfg.dtype)))


def _chunks_then_steps(L: int, chunk: int, steps: int):
    """``(at, n, kept)`` of the calls that take ``L`` positions through
    a cache: all but the last ``steps`` in calls of ``chunk``, then one
    position a call, whose outputs are ``kept``."""
    at = 0
    while at < L:
        n = min(chunk, L - steps - at) if at < L - steps else 1
        yield at, n, at + n > L - steps
        at += n


def program_absorbed(cfg, mla_params, y, chunk: int, steps: int,
                     start: int = 0):
    """The PROGRAM's latent attention mixer alone THROUGH ITS CACHE on
    ``y`` [1, L, D], the inputs of positions ``start .. start + L - 1``
    (the rows lie in the cache from row 0 on: the rotation is relative):
    all but the last ``steps`` positions in calls of ``chunk`` (the
    expanded path over the slab, the ROTATED rows left in the cache),
    then ``steps`` one-token calls on the ABSORBED path (on the chip
    ``latent_append`` and ``latent_attend``) against that prefix, each
    query rotated at its own position.  Returns those steps' outputs
    [steps, D] float32."""
    from edl_tpu.models.transformer import LatentAttention

    L = y.shape[1]
    mixer = LatentAttention(dataclasses.replace(
        cfg, decode=True, max_len=-(-L // 128) * 128))
    y = y[:1].astype(cfg.dtype)
    cache = jax.jit(lambda: _fresh(mixer, y[:, :1], jnp.zeros((1, 1),
                                                             jnp.int32)))()

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(p, cache, rows, first):
        out, mut = mixer.apply(
            {"params": p, "cache": cache}, rows,
            first + jnp.arange(rows.shape[1])[None],
            mutable=["cache", "intermediates"])
        return out[0, -1], mut["cache"]

    outs = []
    for at, n, kept in _chunks_then_steps(L, chunk, steps):
        row, cache = run(mla_params, cache, y[:, at:at + n],
                         jnp.asarray(start + at, jnp.int32))
        if kept:
            outs.append(row)
    return _f32(jnp.stack(outs))


def program_cached(cfg, params, ids, chunk: int, steps: int):
    """The PROGRAM's block THROUGH ITS CACHE over ``ids`` [1, L]: a
    decode model (no engine) prefills all but the last ``steps`` tokens
    in chunks of ``chunk`` with the latent rows carried, then takes the
    last ``steps`` tokens one at a time (on the chip ``latent_append``,
    ``latent_attend`` and ``moe_decode_gmm``).  Returns those steps'
    logits [steps, V] float32."""
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

    out = []
    for at, n, kept in _chunks_then_steps(L, chunk, steps):
        row, cache = run(params, cache, ids[:, at:at + n],
                         jnp.asarray(at, jnp.int32))
        if kept:
            out.append(row)
    return jnp.stack(out)


def held_pairs(conf: dict, chosen: dict, upto: int | None = None) -> int:
    """The host's recount: of the reference router's (token, expert)
    pairs over the first ``upto`` positions, those that land on the
    experts held here, summed over the sparse layers."""
    return int(sum((np.asarray(c)[:, :upto] < conf["n_routed_experts"]).sum()
                   for c in chosen.values()))


@jax.jit
def _selection_scores(gate, y):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(y @ _f32(gate))


def held_swaps(v, held: int, top_k: int, delta: float) -> list:
    """``[(gap, out, in)]``, nearest tie first: the swaps of one chosen
    expert for one unchosen one that change WHICH HELD EXPERTS one token
    computes, among the pairs whose selection scores ``v`` [E] lie
    within ``delta`` of each other (``archs/exaone_moe.held_swaps``,
    copied: an architecture's module stands alone)."""
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
    the same sigmoid router over a held share, so the same heavy tail;
    PERF.md section 6, PR 30).  The search runs only where ``plain`` is
    over ``limit``, stops at the first routing under which the token is
    within ``limit``, and spends at most ``passes`` reference passes.

    Returns ``{"plain", "shortfall", "swaps" [(layer, out, in, gap)],
    "passes"}``."""

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
                v = np.asarray(_selection_scores(
                    params[f"layer_{i}"]["moe"]["gate"], y[None]))[0]
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
    return np.asarray(jnp.linalg.norm(diff, axis=axes)
                      / jnp.maximum(jnp.linalg.norm(want, axis=axes), 1e-30)
                      ).reshape(-1)


CACHE_STEPS = 16


def block_agreement(conf: dict, params, ids, ref: dict, *, cfg=None,
                    program_params=None, tag: str = "",
                    memo: dict | None = None) -> dict:
    """The program's block (``cfg`` and ``program_params`` let a
    deliberately wrong variant stand in) against ``reference``'s ``ref``
    on the same ``ids``, as ``archs/kimi_linear.py`` compares, and
    prints.  Every error is the norm of (program - reference) over the
    norm of the reference's output, a token, each part fed the
    reference's own input to it:

    ``attention_error`` [layers * B * L]: every latent attention mixer
    alone on the EXPANDED path (low-rank query, rotation at positions
    ``0 .. L - 1``).
    ``absorbed_error`` [layers * ``CACHE_STEPS``]: the same mixer
    THROUGH ITS CACHE (``program_absorbed``) on a seeded unit-normal
    input of ``run.absorbed_prefix`` positions from position
    ``run.absorbed_start`` on (the timed documents' far end: angles of
    16k-25k positions): the one-token ABSORBED path against a prefix of
    rotated rows, against the reference's un-absorbed attention of the
    same rows at the same positions.
    ``expert_error`` [sparse layers * B * L], ``routed_error``: every
    expert layer alone, and with the shared expert out of both sides
    (over the tokens that chose a held expert: the others' part is 0).
    ``logit_error_sigma`` [B * L]: the whole block at the level of
    logits, the root mean square over the vocabulary of (program -
    reference) in standard deviations of the reference's logits there.
    ``cache_error_sigma`` [``CACHE_STEPS``]: the same for the block
    THROUGH ITS CACHE (``program_cached``) at the probe's last
    positions.
    ``expert_sets_differ``, ``held_pairs``: as the other expert cells.

    ``memo`` keeps the reference's side of ``absorbed_error`` between
    calls on the same ``params`` and ``ids`` (the variants script's)."""
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
    del own
    experts = np.concatenate([
        _rel(program_experts(cfg, pp[f"layer_{i}"]["moe"], y) - out, out)
        for i, (y, out, _) in ref["experts"].items()])
    # the tokens some held expert computes for: with 16 of 256 experts
    # held, six tokens in ten choose none of them and their part is 0
    routed = np.concatenate([
        _rel(program_experts(cfg, pp[f"layer_{i}"]["moe"], y, shared=False)
             - part, part)[np.linalg.norm(part, axis=-1).reshape(-1) > 0]
        for i, (y, _, part) in ref["experts"].items()])
    attention = np.concatenate([
        _rel(program_attention(cfg, pp[f"layer_{i}"]["mla"], y) - out, out)
        for i, (y, out) in ref["mixers"].items()])
    chunk = conf["run"]["prefill_chunk"]
    n_abs = conf["run"].get("absorbed_prefix", 8192) + CACHE_STEPS
    first = conf["run"].get("absorbed_start", 0)
    long_y = jax.random.normal(
        jax.random.key(int(jnp.sum(ids)) % (1 << 31)),
        (1, n_abs, conf["hidden_size"]), jnp.float32)
    absorbed = []
    memo = {} if memo is None else memo
    for i in ref["mixers"]:
        if i not in memo:
            memo[i] = mla_mixer(conf, params[f"layer_{i}"]["mla"], long_y,
                                last=CACHE_STEPS, start=first)[0]
        out = memo[i]
        absorbed.append(_rel(program_absorbed(
            cfg, pp[f"layer_{i}"]["mla"], long_y, chunk, CACHE_STEPS, first)
            - out, out))
    absorbed = np.concatenate(absorbed)
    cached = program_cached(cfg, pp, ids[:1], chunk, CACHE_STEPS)
    tail = want[0, -CACHE_STEPS:]
    cache_err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(cached - tail), -1))
                           / jnp.std(tail, -1))
    pairs = held_pairs(conf, ref["chosen"])
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: latent attention alone, expanded median "
          f"{np.median(attention):.5f} max {attention.max():.5f} over "
          f"{attention.size} (token, layer) pairs, absorbed against a "
          f"prefix of {n_abs - CACHE_STEPS} rotated rows from position "
          f"{first} median "
          f"{np.median(absorbed):.5f} max {absorbed.max():.5f} over "
          f"{absorbed.size}; expert layers alone median "
          f"{np.median(experts):.5f} mean {experts.mean():.5f} over "
          f"{experts.size}, their held experts alone median "
          f"{np.median(routed):.5f} over {routed.size}; logits, median {np.median(err):.5f} "
          f"mean {err.mean():.5f} max {err.max():.5f} sigma over {err.size} "
          f"positions; through the cache (chunks of {chunk}, then "
          f"{CACHE_STEPS} one-token steps) median {np.median(cache_err):.5f} "
          f"max {cache_err.max():.5f} sigma; expert sets differ in "
          f"{100 * differ:.3f}% of the (token, layer) pairs; {pairs} pairs "
          f"on held experts", flush=True)
    return {"attention_error": attention, "absorbed_error": absorbed,
            "expert_error": experts, "routed_error": routed,
            "logit_error_sigma": err, "cache_error_sigma": cache_err,
            "expert_sets_differ": differ, "held_pairs": pairs}


# -- what the algorithms need, from shapes alone ------------------------------
def latent_layers(conf: dict) -> int:
    return conf["num_hidden_layers"]


def sparse_layers(conf: dict) -> int:
    return mlp_kinds(conf).count("sparse")


def expert_params(conf: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def expert_flops_per_assignment(conf: dict) -> float:
    """One (token, expert) pair: three matmuls, 2 FLOPs a weight."""
    return 2.0 * expert_params(conf)


def mla_matmul_params(conf: dict) -> int:
    d, h = conf["hidden_size"], conf["num_attention_heads"]
    qr, rank, nope, rope_d, vd = (
        conf["q_lora_rank"], conf["kv_lora_rank"], conf["qk_nope_head_dim"],
        conf["qk_rope_head_dim"], conf["v_head_dim"])
    return (d * qr + qr * h * (nope + rope_d) + d * (rank + rope_d)
            + rank * h * (nope + vd) + h * vd * d)


def latent_width(conf: dict) -> int:
    """Values a latent layer caches a token: ``c | k_pe``."""
    return conf["kv_lora_rank"] + conf["qk_rope_head_dim"]


def shared_matmul_params(conf: dict) -> int:
    """Read by every token, all layers together: the mixers, the dense
    layers' MLPs, the routers and the shared experts."""
    d = conf["hidden_size"]
    dense = conf["num_hidden_layers"] - sparse_layers(conf)
    return (latent_layers(conf) * mla_matmul_params(conf)
            + dense * 3 * d * conf["intermediate_size"]
            + sparse_layers(conf)
            * (d * _router_width(conf) + 3 * d * conf["n_shared_experts"]
               * conf["moe_intermediate_size"]))


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """The only cache there is: one row ``c | k_pe`` a token a layer,
    keys and values the same bytes (the architecture's 576 values; the
    program keeps them in rows of 640, ``kv_slot_bytes_latent``)."""
    return latent_width(conf) * itemsize * latent_layers(conf)


def param_count(conf: dict) -> int:
    """Every parameter this device holds (``n_routed_experts`` routed
    experts a sparse layer, the router whole, the vocabulary slice for
    the embedding and for the head)."""
    d = conf["hidden_size"]
    dense = conf["num_hidden_layers"] - sparse_layers(conf)
    return (2 * conf["vocab_size"] * d + d
            + latent_layers(conf) * (mla_matmul_params(conf)
                                     + conf["kv_lora_rank"]
                                     + conf["q_lora_rank"])
            + conf["num_hidden_layers"] * 4 * d
            + dense * 3 * d * conf["intermediate_size"]
            + sparse_layers(conf)
            * (d * _router_width(conf)
               + 3 * d * conf["n_shared_experts"]
               * conf["moe_intermediate_size"]
               + conf["n_routed_experts"] * expert_params(conf)))


def active_matmul_params(conf: dict, held_share: float | None = None
                         ) -> float:
    """Matmul parameters EVERY token of a multi-token call meets on this
    device: the mixers, the dense MLPs, the routers and the shared
    experts whole, and of its ``num_experts_per_tok`` routed experts the
    share that lands on held ones (``held_share``; by default the held
    fraction of the router's width).  The head is not among them: a
    call needs it for its last row alone (the chunk lane's ``mid``
    never runs it)."""
    if held_share is None:
        held_share = conf["n_routed_experts"] / _router_width(conf)
    return (shared_matmul_params(conf)
            + sparse_layers(conf) * conf["num_experts_per_tok"] * held_share
            * expert_params(conf))


def decode_step_min_bytes(conf: dict, experts_touched: float,
                          live_tokens: float, itemsize: int = 2,
                          live_slots: float = 0.0) -> float:
    """What one decode token step must read at least: the mixers', dense
    MLPs', routers', shared experts' and head's weights once, the held
    experts its batch touched (a layer's mean) in every sparse layer,
    and the latent layers' live rows.  ``live_slots`` is taken and
    unused: no layer keeps a recurrent state."""
    del live_slots
    shared = (shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = sparse_layers(conf) * experts_touched * expert_params(conf)
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens)


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


def latent_attention_min(conf: dict, positions: float, pairs: float,
                         itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of the ``latent_append`` and ``latent_attend``
    kernels for ``positions`` live positions (summed over live slots,
    token steps and latent layers) read by ``pairs`` (slot, token step,
    layer) calls: each position's row ``c | k_pe`` read ONCE for all
    heads (keys and values are the same bytes), every head's score
    against it and its part in the value sum at 2 FLOPs a multiply-add;
    a call's own row written and its queries read and outputs written.
    At 128 heads a row of 1,152 bytes carries 278,528 FLOPs, 242 a byte:
    at this chip's ridge, so a reader takes the greater of the two."""
    h, w = conf["num_attention_heads"], latent_width(conf)
    flops = positions * h * 2.0 * (w + conf["kv_lora_rank"])
    own = pairs * (w + 2 * h * w) * itemsize
    return flops, positions * w * itemsize + own


def chunk_prefill_flops(conf: dict, tokens: float, pairs: float,
                        rows_expanded: float,
                        held_share: float | None = None) -> float:
    """FLOPs the multi-token (chunk, prefill, reuse) programs need for
    ``tokens`` real tokens whose queries see ``pairs`` (query, row)
    pairs a layer in all (summed over the latent layers), the programs
    having to expand ``rows_expanded`` latent rows (a row once a call a
    layer, summed over calls and layers) to ``k_nope | v`` for every
    head: 2 FLOPs a weight a token (``active_matmul_params``: the head,
    one row a call, is left out); a pair's score over ``nope + rope``
    dims and its part in the value sum over ``v``, every head; a row's
    expansion ``rank x H x (nope + v)``."""
    h = conf["num_attention_heads"]
    nope, rope_d, vd = (conf["qk_nope_head_dim"], conf["qk_rope_head_dim"],
                        conf["v_head_dim"])
    return (2.0 * active_matmul_params(conf, held_share) * tokens
            + pairs * h * 2.0 * (nope + rope_d + vd)
            + rows_expanded * 2.0 * conf["kv_lora_rank"] * h * (nope + vd))
