"""OLMoE for the benchmark: configuration, weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module:
``runners/serve_arch.py`` registers it as ``model`` and its
``reference`` as ``reference``, and ``runners/serve.py`` then calls
``transformer_config``, ``init_params`` and ``logits`` exactly as it
calls ``model.py`` and ``reference.py``.  ``block_agreement`` is what
the cell's ``correct`` also rests on: the program's own block, expert
layer by expert layer and as a whole, against the reference on the
probe.

The reference is the published OLMoE layer (``modeling_olmoe.py``) in
straight ``jax.numpy``, float32, ``default_matmul_precision("highest")``:
no kernels, no cache, no sort - every expert is applied to every token
and weighted by a dense ``[tokens, experts]`` matrix that is zero where
the token did not choose the expert.  Epsilon is the published 1e-5,
``norm_topk_prob`` is read from the file (false as published).  Two
departures, the ones ``reference.py`` documents for Mistral: RoPE
rotates interleaved pairs (x[2i], x[2i+1]) where the published code
rotates half-split pairs, and q, k, v come from one fused ``attn_qkv``
matrix - both a fixed permutation of random weights.  It takes the
program's parameter tree in whatever type it is stored in and casts one
layer's attention and ONE expert at a time to float32 (a whole layer in
float32 is 1.7 GB beside a 12.7 GB engine).

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# every key of the published config.json the catalog keeps, and the
# benchmark's own; a key outside both is refused, not ignored
PUBLISHED = {"attention_bias", "clip_qkv", "hidden_act", "hidden_size",
             "intermediate_size", "max_position_embeddings", "model_type",
             "norm_topk_prob", "num_attention_heads", "num_experts",
             "num_experts_per_tok", "num_hidden_layers",
             "num_key_value_heads", "rms_norm_eps", "rope_scaling",
             "rope_theta", "tie_word_embeddings", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "deployment", "run", "memory", "sizing_notes"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/olmoe.py maps no key {unknown}: a key it "
                         f"ignored would run another model under this name")
    want = {"model_type": "olmoe", "hidden_act": "silu",
            "attention_bias": False, "clip_qkv": None, "rope_scaling": None}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    heads = conf["num_attention_heads"]
    if conf["hidden_size"] % heads:
        raise ValueError("hidden_size is not a multiple of the heads")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["run"]["compute_dtype"]]
    kw = dict(vocab_size=conf["vocab_size"],
              num_layers=conf["num_hidden_layers"],
              embed_dim=conf["hidden_size"], num_heads=heads,
              num_kv_heads=conf["num_key_value_heads"],
              mlp_dim=conf["intermediate_size"], max_len=max_len,
              rope_theta=float(conf["rope_theta"]),
              tie_embeddings=bool(conf["tie_word_embeddings"]), dtype=dtype,
              attention_impl=conf["run"].get("attention", "auto"),
              norm_eps=float(conf["rms_norm_eps"]), qk_norm=True,
              moe_experts=conf["num_experts"],
              moe_top_k=conf["num_experts_per_tok"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=bool(conf["norm_topk_prob"]))
    kw.update(overrides)
    return TransformerConfig(**kw)


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = False):
    """The parameter tree on the device from the seed, one layer per
    jitted call and cast inside it: a whole model made in float32 and
    cast afterwards would need 10.9 GB for a moment.  ``layer_<i>``
    (``split_layers``) or stacked ``layers``.

    The layers' matrices are the program's own initialisers, with
    three corrections without which the comparison with the reference
    is blind to the expert layers (my chip runs 2-3, PR 26: a
    renormalised gate, dropped assignments and int8 experts all read
    the same shortfall as the right program).  Each expert matrix is
    lecun-normal BY ITSELF: ``MoEMLP``'s initialiser counts the expert
    axis as a receptive field (fan_in = E * M), so its matrices come
    out sqrt(E) too small and a gated expert's output 1/512 of a dense
    MLP's at 64 experts (measured: norm 0.0065 beside attention's 7);
    they are scaled back by sqrt(E) here.  Embedding rows are unit
    normal, a residual stream of order 1 an element as a trained model
    has: with flax's default (1 / sqrt(hidden)) the first attention's
    average over the context swamps the token and every token of a
    prompt routes to the same few experts
    (``moe_prefill_load_imbalance`` read 7.2).  Norm scales are
    1 + 0.1 * normal, so that a misplaced scale shows."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]
    D, V = cfg.embed_dim, cfg.vocab_size

    def cast(path, a, key):
        if path[-1].key == "scale":
            a = 1.0 + 0.1 * jax.random.normal(key, a.shape, jnp.float32)
        elif a.ndim == 3:                       # [experts, in, out]
            a = a * a.shape[0] ** 0.5
        return a.astype(dt)

    def scaled(tree, key):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [cast(p, a, k) for (p, a), k in zip(leaves, keys)])

    @jax.jit
    def layer(key):
        k1, k2 = jax.random.split(key)
        p = Block(cfg).init(k1, jnp.zeros((1, 8, D), cfg.dtype),
                            jnp.zeros((1, 8), jnp.int32))["params"]
        return scaled(p, k2)

    @jax.jit
    def ends(key):
        k1, k2, k3 = jax.random.split(key, 3)
        out = {"tok_embed": {"embedding": jax.random.normal(k1, (V, D))},
               "final_norm": {"scale": jnp.ones((D,))}}
        if not cfg.tie_embeddings:
            out["lm_head"] = {"kernel":
                              nn.initializers.lecun_normal()(k2, (D, V))}
        return scaled(out, k3)

    keys = jax.random.split(jax.random.key(seed % (1 << 31)),
                            cfg.num_layers + 1)
    params = ends(keys[0])
    layers = [layer(k) for k in keys[1:]]
    if split_layers:
        params.update({f"layer_{i}": p for i, p in enumerate(layers)})
    else:
        params["layers"] = jax.tree.map(lambda *a: jnp.stack(a), *layers)
    return params


# -- the reference -----------------------------------------------------------
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
        out = out.reshape(b, l, d)
        return x + out, chosen.reshape(b, l, top_k), y, out


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
    top_k], experts [layers] of (input, output) [B, L, D])``: the final
    hidden states, every layer's expert choice, and what went into and
    came out of every expert layer."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    routes, experts = [], []
    for p in _layers(params, conf["num_hidden_layers"]):
        x, chosen, y, out = _layer(
            x, p, heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"],
            theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
            top_k=conf["num_experts_per_tok"],
            norm_topk=bool(conf["norm_topk_prob"]))
        routes.append(chosen)
        experts.append((y, out))
    return x, jnp.stack(routes), experts


def hidden(conf: dict, params, ids):
    """Final hidden states [B, L, D] before the last norm."""
    return forward(conf, params, ids)[0]


def reference(conf: dict, params, ids) -> dict:
    """The full forward pass: ``logits`` [B, L, V] float32, ``chosen``
    [layers, B, L, top_k] and ``experts`` (``forward``)."""
    if conf.get("tie_word_embeddings"):
        w = params["tok_embed"]["embedding"].T
    else:
        w = params["lm_head"]["kernel"]
    x, chosen, experts = forward(conf, params, ids)
    return {"logits": _head(x, params["final_norm"]["scale"], w,
                            eps=float(conf["rms_norm_eps"])),
            "chosen": chosen, "experts": experts}


def logits(conf: dict, params, ids):
    """[B, L, V] float32 logits of the full forward pass."""
    return reference(conf, params, ids)["logits"]


def program_forward(cfg, params, ids):
    """The PROGRAM's block over ``ids``: ``edl_tpu``'s ``Block`` layer
    by layer, its final norm and head, in ``cfg``'s compute type (full
    forward, dense attention, no cache).  Returns ``(logits [B, L, V]
    float32, chosen [layers, B, L, top_k])``, the experts each layer's
    float32 router picked from the block's own ``mlp_norm`` output."""
    import flax.linen as nn

    from edl_tpu.models.transformer import Block, RMSNorm

    pos = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
    x = jnp.take(params["tok_embed"]["embedding"], ids, axis=0).astype(
        cfg.dtype)
    routes = []
    for p in _layers(params, cfg.num_layers):
        (x, _), seen = Block(cfg).apply(
            {"params": p}, x, pos, mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "mlp_norm")
        y = seen["intermediates"]["mlp_norm"]["__call__"][0]
        probs = jax.nn.softmax(_f32(y) @ _f32(p["moe"]["gate"]), axis=-1)
        routes.append(jax.lax.top_k(probs, cfg.moe_top_k)[1])
    x = RMSNorm(cfg.dtype, cfg.norm_eps).apply(
        {"params": params["final_norm"]}, x)
    if cfg.tie_embeddings:
        out = x @ params["tok_embed"]["embedding"].T.astype(cfg.dtype)
    else:
        out = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype).apply(
            {"params": params["lm_head"]}, x)
    return _f32(out), jnp.stack(routes)


def program_experts(cfg, moe_params, y):
    """The PROGRAM's expert layer alone (``ops/moe.py``'s ``MoEMLP`` as
    ``Block`` builds it) on ``y`` [B, L, D], in ``cfg``'s compute type."""
    from edl_tpu.ops.moe import MoEMLP

    layer = MoEMLP(num_experts=cfg.moe_experts, mlp_dim=cfg.mlp_dim,
                   top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity,
                   dtype=cfg.dtype, gated=cfg.moe_gated,
                   norm_topk=cfg.moe_norm_topk)
    (out, _), _ = layer.apply({"params": moe_params}, y.astype(cfg.dtype),
                              mutable=["intermediates"])
    return _f32(out)


def block_agreement(conf: dict, params, ids, ref: dict, *, cfg=None,
                    program_params=None, tag: str = "") -> dict:
    """The program's block (``cfg`` and ``program_params`` let a
    deliberately wrong variant stand in) against ``reference``'s ``ref``
    on the same ``ids``, three ways, and prints them:

    ``expert_error`` [layers * B * L]: every expert layer ALONE, fed
    the reference's own input to that layer: the norm of (program -
    reference) over the norm of the reference's output, a token.  The
    compute type's rounding passes through one layer only, so what the
    layer itself does to precision stands out: experts rounded to int8
    read several times the honest figure, a dropped assignment or a
    renormalised gate tens of times (PERF.md section 6, PR 26).  The
    median over tokens and layers, because a token whose 8th and 9th
    expert swap on the rounded input is far out and honest.
    ``logit_error_sigma`` [B * L]: the whole block (``program_forward``)
    at the level of logits: at every position the root mean square over
    the vocabulary of (program - reference), in standard deviations of
    the reference's logits there.  It sees every layer, attention
    included, through six layers of the compute type's rounding.
    ``expert_sets_differ``: the share of (token, layer) pairs whose
    expert set in the whole block differs from the reference's (a
    near-tie between the 8th and 9th expert is the one honest way)."""
    import numpy as np

    cfg = cfg or transformer_config(conf, max_len=ids.shape[1], remat=False,
                                    attention_impl="dense")
    program_params = params if program_params is None else program_params
    own, picked = program_forward(cfg, program_params, ids)
    want = ref["logits"]
    differ = float((jnp.sort(picked, -1) != jnp.sort(ref["chosen"], -1))
                   .any(-1).mean())
    err = np.asarray(jnp.sqrt(jnp.mean(jnp.square(own - want), -1))
                     / jnp.std(want, -1)).reshape(-1)
    alone = []
    for p, (y, out) in zip(_layers(program_params, cfg.num_layers),
                           ref["experts"]):
        diff = program_experts(cfg, p["moe"], y) - out
        alone.append(np.asarray(jnp.linalg.norm(diff, axis=-1)
                                / jnp.linalg.norm(out, axis=-1)).reshape(-1))
    alone = np.concatenate(alone)
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: expert layers alone, error over norm, median "
          f"{np.median(alone):.5f} mean {alone.mean():.5f} over {alone.size} "
          f"(token, layer) pairs; logits, median {np.median(err):.5f} mean "
          f"{err.mean():.5f} max {err.max():.5f} sigma over {err.size} "
          f"positions; expert sets differ in {100 * differ:.3f}% of "
          f"{picked[..., 0].size} (token, layer) pairs", flush=True)
    return {"expert_error": alone, "logit_error_sigma": err,
            "expert_sets_differ": differ}


# -- what the algorithms need, from shapes alone ------------------------------
def expert_params(conf: dict) -> int:
    """One expert: gate, up and down projections."""
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def expert_flops_per_assignment(conf: dict) -> float:
    """One (token, expert) pair: three matmuls, 2 FLOPs a weight."""
    return 2.0 * expert_params(conf)


def layer_shared_matmul_params(conf: dict) -> int:
    """Per layer, read by every token: attention and the router."""
    d = conf["hidden_size"]
    h, hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = d // h
    return d * (h + 2 * hk) * dh + h * dh * d + d * conf["num_experts"]


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    dh = conf["hidden_size"] // conf["num_attention_heads"]
    return (2 * conf["num_key_value_heads"] * dh * itemsize
            * conf["num_hidden_layers"])


def param_count(conf: dict) -> int:
    d, layers = conf["hidden_size"], conf["num_hidden_layers"]
    tied = conf.get("tie_word_embeddings", False)
    per_layer = (layer_shared_matmul_params(conf)
                 + conf["num_experts"] * expert_params(conf)
                 + 2 * d            # attn_norm, mlp_norm
                 + 2 * d)           # q_norm, k_norm (MHA: both hidden wide)
    return (conf["vocab_size"] * d * (1 if tied else 2) + layers * per_layer
            + d)


def decode_step_min_bytes(conf: dict, experts_touched: float,
                          live_tokens: float, itemsize: int = 2) -> float:
    """What one decode token step must read at least: attention, router
    and head weights once, the experts its batch touched (a layer's
    mean) in every layer, and the live keys and values."""
    shared = (conf["num_hidden_layers"] * layer_shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = (conf["num_hidden_layers"] * experts_touched
               * expert_params(conf))
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens)


def expert_matmul_min(conf: dict, assignments: float, experts_read: float,
                      itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` the grouped matmuls need for ``assignments``
    (token, expert) pairs that made the program read ``experts_read``
    expert weight sets (summed over layers and programs): the weights
    once, and each pair's input row read and output row written for the
    three projections (hidden in, 2 x width out; width in, hidden out)."""
    d, m = conf["hidden_size"], conf["intermediate_size"]
    rows = assignments * (d + 2 * m + m + d) * itemsize
    return (assignments * expert_flops_per_assignment(conf),
            experts_read * expert_params(conf) * itemsize + rows)
