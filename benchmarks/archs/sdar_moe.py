"""SDAR-MoE (``model_type: sdar_moe``) for the benchmark: configuration,
weights, reference, generation by diffusion over blocks, counts.

One architecture's ``model`` and ``reference`` in one module, as
``archs/olmoe.py`` is: ``transformer_config`` and ``init_params`` build
the PROGRAM's model from the configuration file, everything below them
is the plain reference and has no ``edl_tpu`` in it.

The layer, as the published ``config.json`` gives it (48 alike)::

    h <- h + Attn(RMSNorm(h)),  h <- h + MoE(RMSNorm(h)),
    logits = W_head RMSNorm(h_n)                    (untied, eps 1e-6)
    Attn: q = x W_q -> [32, 128], k, v -> [4, 128], no biases;
          q, k <- RMSNorm_128 per head, BEFORE RoPE (theta 1e6, all
          128 dims); scores q.k / sqrt(128) under the mask M; query
          head h reads KV head h // 8; out = concat(heads) W_o
    MoE:  p = softmax(x W_r) over 128; S = top-8 of p; w_e = p_e /
          sum_S p (norm_topk_prob); y = sum_S w_e W_down,e(silu(x
          W_gate,e) * (x W_up,e)), expert width 768
    M[i, j] = 1 iff floor(j / L) <= floor(i / L)   (block-causal)

The reference is straight ``jax.numpy``, float32,
``default_matmul_precision("highest")``: no kernels, no cache, no sort,
no batching; every expert is applied to every token and weighted by a
dense ``[tokens, experts]`` matrix.  ONE jitted program serves the like
layers, the layer's weights its arguments (a program a layer would be
six compiles of the same thing).  It takes the program's parameter tree
in whatever type it is stored in and casts one layer's attention and
ONE expert at a time to float32.  Two departures from the published
modelling code, both a fixed permutation of random weights: RoPE
rotates interleaved pairs ``(x[2i], x[2i+1])`` where the published code
rotates half-split pairs, and q, k, v come from one fused ``attn_qkv``
matrix.

``block_diffusion_generate`` is JetLM's loop of that name as ISSUE 48
sets it out (the configuration file's ``assumed`` names each value's
origin), recomputing the WHOLE sequence every pass: no cache, so what
the serving path keeps or overwrites in one cannot be wrong here.

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

PUBLISHED = {"attention_bias", "decoder_sparse_step", "head_dim",
             "hidden_act", "hidden_size", "intermediate_size",
             "max_position_embeddings", "max_window_layers",
             "mlp_only_layers", "model_type", "moe_intermediate_size",
             "norm_topk_prob", "num_attention_heads", "num_experts",
             "num_experts_per_tok", "num_hidden_layers",
             "num_key_value_heads", "rms_norm_eps", "rope_scaling",
             "rope_theta", "sliding_window", "tie_word_embeddings",
             "use_sliding_window", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "deployment", "run", "memory", "sizing_notes"}

# EVERY weight comes from these keys whatever ``--seed`` is; the seed
# draws the token ids (prompts, probes, the checks' blocks) and nothing
# else.  PERF.md section 7, PR 40 (a) found a seed's router moving the
# expert work and fixed the routers' key; here the whole stack decides
# the work: on random weights a greedy block-diffusion answer is a few
# tokens repeated, how few is the weights' doing, and a block's
# positions that carry one embedding (the mask's, a repeated token's)
# route alike, so the experts a pass touches (45 of 128 at 6 live slots
# where 40 independent tokens would touch 100) and with them the pass
# time followed the seed: six seeds of seeded weights read p50 2.14-2.40
# s, spread 8.2% of a bound whose half is 5% (PERF.md section 6, PR 48).
ROUTER_KEY = 20261003
WEIGHTS_KEY = 20261004


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/sdar_moe.py maps no key {unknown}: a key it "
                         f"ignored would run another model under this name")
    want = {"model_type": "sdar_moe", "hidden_act": "silu",
            "attention_bias": False, "rope_scaling": None,
            "decoder_sparse_step": 1, "mlp_only_layers": [],
            "use_sliding_window": False, "sliding_window": None,
            "tie_word_embeddings": False}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    rc = conf["run"]
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        rc["compute_dtype"]]
    kw = dict(vocab_size=conf["vocab_size"],
              num_layers=conf["num_hidden_layers"],
              embed_dim=conf["hidden_size"],
              num_heads=conf["num_attention_heads"],
              num_kv_heads=conf["num_key_value_heads"],
              attn_head_dim=conf["head_dim"],
              mlp_dim=conf["intermediate_size"],
              moe_mlp_dim=conf["moe_intermediate_size"], max_len=max_len,
              rope_theta=float(conf["rope_theta"]), tie_embeddings=False,
              dtype=dtype, attention_impl=rc.get("attention", "auto"),
              norm_eps=float(conf["rms_norm_eps"]), qk_norm=True,
              qk_norm_per_head=True, moe_experts=conf["num_experts"],
              moe_top_k=conf["num_experts_per_tok"], moe_capacity=0.0,
              moe_gated=True, moe_norm_topk=bool(conf["norm_topk_prob"]),
              moe_router="softmax")
    # the generation loop's settings ride with the model's configuration:
    # whoever builds an engine over it builds a block engine
    gen = generation(conf)
    kw.update(block_length=gen["block_length"],
              block_steps=gen["denoising_steps"],
              block_remasking=gen["remasking"],
              block_threshold=gen["confidence_threshold"],
              block_mask_id=gen["mask_id"])
    kw.update(overrides)
    return TransformerConfig(**kw)


def generation(conf: dict) -> dict:
    """The generation loop's settings as the configuration's ``run``
    block states them: ``block_diffusion_generate``'s arguments, and
    what ``transformer_config`` hands the program
    (``confidence_threshold`` is ``dynamic`` remasking's; a ``static``
    configuration need not state it)."""
    rc = conf["run"]
    return {"block_length": int(rc["block_length"]),
            "denoising_steps": int(rc["denoising_steps"]),
            "remasking": rc["remasking"],
            "confidence_threshold": float(rc.get("confidence_threshold",
                                                 0.9)),
            "mask_id": int(rc["mask_token_id"])}


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device, one layer per jitted call and
    cast inside it (``layer_<i>``, or stacked ``layers``), as
    ``archs/olmoe.py`` makes them: the program's own initialisers with
    PR 26's corrections (each expert matrix lecun-normal BY ITSELF,
    norm scales 1 + 0.1 normal, embedding rows unit normal under an
    untied lecun-normal head).  ``seed`` draws NO leaf (the comment
    at ``WEIGHTS_KEY``): every leaf comes from ``WEIGHTS_KEY``, the
    routers' ``gate`` from ``ROUTER_KEY`` and the layer's number, so
    every run of the cell serves one model and the seed chooses what
    it is asked."""
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
    def layer(key, i):
        k1, k2 = jax.random.split(key)
        p = Block(cfg).init(k1, jnp.zeros((1, 8, D), cfg.dtype),
                            jnp.zeros((1, 8), jnp.int32))["params"]
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

    del seed
    keys = jax.random.split(jax.random.key(WEIGHTS_KEY), cfg.num_layers + 1)
    params = ends(keys[0])
    layers = [layer(k, i) for i, k in enumerate(keys[1:])]
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
    the experts, the ``top_k`` largest kept and renormalised
    (``norm_topk``), every expert's gated SiLU FFN weighted by what the
    token gave it."""
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
    return out


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "dh", "theta", "eps", "top_k", "norm_topk",
    "block", "scores"))
def _layer(x, p, *, heads, kv_heads, dh, theta, eps, top_k, norm_topk,
           block, scores="float32"):
    """One layer on ``x [B, L, D]`` under the block-causal mask of
    block length ``block`` (1: causal).  ``scores``: the type the
    attention scores and probabilities are held in (the reference's is
    float32; ``bfloat16`` is the nearest precision below the stated one,
    for the limits' second reading)."""
    with jax.default_matmul_precision("highest"):
        b, l, _ = x.shape
        y = _rmsnorm(x, p["attn_norm"]["scale"], eps)
        qkv = y @ _f32(p["attn_qkv"]["kernel"])
        q, k, v = jnp.split(qkv, [heads * dh, (heads + kv_heads) * dh], -1)
        # q_norm / k_norm: over each head's 128 dims, before RoPE
        q = _rmsnorm(q.reshape(b, l, heads, dh), p["q_norm"]["scale"], eps)
        k = _rmsnorm(k.reshape(b, l, kv_heads, dh), p["k_norm"]["scale"],
                     eps)
        q, k = _rope(q, theta), _rope(k, theta)
        v = v.reshape(b, l, kv_heads, dh)
        g = heads // kv_heads
        k = jnp.repeat(k, g, axis=2)       # q head h reads kv head h // g
        v = jnp.repeat(v, g, axis=2)
        st = jnp.dtype(scores)

        def attend(args):
            # one block of queries against the whole context
            qb, start = args
            s = jnp.einsum("bqhd,bkhd->bhqk", qb.astype(st), k.astype(st))
            s = s * jnp.asarray(dh ** -0.5, st)
            rows = start + jnp.arange(qb.shape[1])
            # M: row i sees column j iff j // block <= i // block
            seen = ((rows // block + 1) * block)[:, None] \
                > jnp.arange(l)[None, :]
            s = jnp.where(seen, s, -jnp.inf)
            w = jax.nn.softmax(s, -1)
            return _f32(jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(st)))

        nb = l // Q_BLOCK if l > Q_BLOCK and l % Q_BLOCK == 0 else 1
        qs = q.reshape(b, nb, l // nb, heads, dh).swapaxes(0, 1)
        a = jax.lax.map(attend, (qs, jnp.arange(nb) * (l // nb)))
        a = a.swapaxes(0, 1).reshape(b, l, heads * dh)
        x = x + a @ _f32(p["attn_out"]["kernel"])
        y = _rmsnorm(x, p["mlp_norm"]["scale"], eps)
        out = _moe(y.reshape(b * l, -1), p["moe"], top_k=top_k,
                   norm_topk=norm_topk).reshape(x.shape)
        return x + out, y, out


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, norm_scale, w, *, eps):
    with jax.default_matmul_precision("highest"):
        return _rmsnorm(x, norm_scale, eps) @ _f32(w)


def _layers(params, n):
    if "layers" in params:
        return [jax.tree.map(lambda a: a[i], params["layers"])
                for i in range(n)]
    return [params[f"layer_{i}"] for i in range(n)]


def reference(conf: dict, params, ids, *, block_length: int | None = None,
              scores: str = "float32", rows=None) -> dict:
    """The full forward pass over ``ids [B, L]`` under ``M``
    (``block_length`` None: the configuration's; 1: causal):
    ``logits`` float32, ``[B, L, V]`` or, with ``rows`` (a slice), those
    positions alone (151,936 float32 logits a row is 0.6 MB), and
    ``experts``, every layer's (input, output) ``[B, L, D]`` of its
    expert FFN."""
    block = generation(conf)["block_length"] if block_length is None \
        else block_length
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    experts = []
    for p in _layers(params, conf["num_hidden_layers"]):
        x, y, out = _layer(
            x, p, heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"], dh=conf["head_dim"],
            theta=float(conf["rope_theta"]), eps=float(conf["rms_norm_eps"]),
            top_k=conf["num_experts_per_tok"],
            norm_topk=bool(conf["norm_topk_prob"]),
            block=max(1, int(block)), scores=scores)
        experts.append((y, out))
    if rows is not None:
        x = x[:, rows]
    return {"logits": _head(x, params["final_norm"]["scale"],
                            params["lm_head"]["kernel"],
                            eps=float(conf["rms_norm_eps"])),
            "experts": experts}


def logits(conf: dict, params, ids, **kw):
    """``reference``'s logits."""
    return reference(conf, params, ids, **kw)["logits"]


def program_experts(cfg, moe_params, y):
    """The PROGRAM's expert layer alone (``ops/moe.py``'s ``MoEMLP`` as
    ``Block`` builds it) on ``y [B, L, D]``, in ``cfg``'s compute type."""
    from edl_tpu.ops.moe import MoEMLP

    layer = MoEMLP(num_experts=cfg.moe_experts, mlp_dim=cfg.expert_dim,
                   top_k=cfg.moe_top_k, capacity_factor=cfg.moe_capacity,
                   dtype=cfg.dtype, gated=cfg.moe_gated,
                   norm_topk=cfg.moe_norm_topk, router=cfg.moe_router)
    (out, _), _ = layer.apply({"params": moe_params}, y.astype(cfg.dtype),
                              mutable=["intermediates"])
    return _f32(out)


def expert_error(cfg, params, ref: dict, *, weights=None,
                 by_reference: bool = False):
    """Every expert layer ALONE, fed the reference's own input to that
    layer: the norm of (program - reference) over the norm of the
    reference's output, a token: ``[layers * B * L]``
    (``archs/olmoe.block_agreement``'s measure of that name).
    ``weights`` (a function of one layer's ``moe`` parameters, applied a
    layer at a time: a rounded copy of a whole stack does not fit beside
    it) stands other expert weights in; ``by_reference``: the
    REFERENCE's own expert layers compute them, not the program's (with
    rounded ``weights``: what the nearest precision below the stated
    one reads on this scale)."""
    import numpy as np

    errs = []
    moe = jax.jit(functools.partial(_moe, top_k=cfg.moe_top_k,
                                    norm_topk=cfg.moe_norm_topk))
    for p, (y, out) in zip(_layers(params, cfg.num_layers), ref["experts"]):
        w = p["moe"] if weights is None else weights(p["moe"])
        if by_reference:
            with jax.default_matmul_precision("highest"):
                got = moe(y.reshape(-1, y.shape[-1]), w).reshape(out.shape)
        else:
            got = program_experts(cfg, w, y)
        errs.append(np.asarray(jnp.linalg.norm(got - out, axis=-1)
                               / jnp.linalg.norm(out, axis=-1)).reshape(-1))
    return np.concatenate(errs)


def unmask_choice(conf_row, masked, n: int, remasking: str,
                  threshold: float):
    """Which masked positions of one block a denoise pass unmasks, from
    each position's confidence ``conf_row [L]``: the ``n`` of highest
    confidence (``static``: ``low_confidence_static``), or every one
    over ``threshold`` and at least the highest (``dynamic``).  Ties go
    to the lower position.  Returns a list of positions."""
    cand = sorted((i for i in range(len(masked)) if masked[i]),
                  key=lambda i: (-float(conf_row[i]), i))
    if remasking == "dynamic":
        return [i for j, i in enumerate(cand)
                if j == 0 or float(conf_row[i]) > threshold]
    return cand[:n]


def block_diffusion_generate(conf: dict, params, prompt, max_new: int, *,
                             block_length: int, denoising_steps: int,
                             mask_id: int,
                             remasking: str = "static",
                             confidence_threshold: float = 0.9,
                             trace: list | None = None,
                             forced: dict | None = None):
    """Greedy generation by diffusion over blocks, the whole sequence
    recomputed every pass.  ``prompt`` (a list of ids) of ``P`` tokens:
    its first ``P0 = floor(P / L) * L`` stand, the rest open the first
    block as given tokens beside masks.  For each block: while a
    position is masked, one forward pass of ``[committed rows, the
    block]`` under ``M``, masked positions fed ``mask_id``; ``x0 =
    argmax``, ``conf = softmax(logits)[x0]``; unmask
    (``unmask_choice``).  An unmasked position is never masked again.
    No shift: the logits AT a masked position are the distribution of
    the token at that position.  Returns the ``max_new`` tokens after
    the prompt (the last block is cut).

    ``trace`` (a list) receives one record a denoise pass: ``{"block",
    "masked" (before), "logits" [L, V], "x0", "conf", "chosen"}``.
    ``forced`` maps (block, pass) to the ``[(position, token)]`` to
    unmask INSTEAD of the loop's own choice (the reference FED another
    generator's decisions; the record still holds its own)."""
    import numpy as np

    L = int(block_length)
    n_unmask = -(-L // int(denoising_steps))
    prompt = [int(t) for t in prompt]
    P0 = len(prompt) // L * L
    done, given = prompt[:P0], prompt[P0:]
    out: list[int] = []
    want = len(given) + max_new
    blk = 0
    while len(out) < want:
        tok = given + [mask_id] * (L - len(given)) if blk == 0 \
            else [mask_id] * L
        masked = [i >= len(given) for i in range(L)] if blk == 0 \
            else [True] * L
        step = 0
        while any(masked):
            ids = jnp.asarray([done + out + [
                mask_id if m else t for t, m in zip(tok, masked)]],
                jnp.int32)
            row = np.asarray(logits(conf, params, ids, block_length=L,
                                    rows=slice(ids.shape[1] - L, None))[0])
            x0 = row.argmax(-1)
            lse = np.log(np.exp(row - row.max(-1, keepdims=True)).sum(-1)) \
                + row.max(-1)
            cf = np.exp(row[np.arange(L), x0] - lse)
            chosen = unmask_choice(cf, masked, n_unmask, remasking,
                                   confidence_threshold)
            if trace is not None:
                trace.append({"block": blk, "masked": list(masked),
                              "logits": row, "x0": x0, "conf": cf,
                              "chosen": list(chosen)})
            picks = [(i, int(x0[i])) for i in chosen]
            if forced is not None and (blk, step) in forced:
                picks = forced[(blk, step)]
            for i, t in picks:
                tok[i], masked[i] = int(t), False
            step += 1
        out += tok
        blk += 1
    return out[len(given):len(given) + max_new]


# -- what the algorithms need, from shapes alone ------------------------------
def expert_params(conf: dict) -> int:
    """One expert: gate, up and down projections."""
    return 3 * conf["hidden_size"] * conf["moe_intermediate_size"]


def layer_shared_matmul_params(conf: dict) -> int:
    """Per layer, read by every token: attention and the router."""
    d, dh = conf["hidden_size"], conf["head_dim"]
    h, hk = conf["num_attention_heads"], conf["num_key_value_heads"]
    return d * (h + 2 * hk) * dh + h * dh * d + d * conf["num_experts"]


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    return (2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize
            * conf["num_hidden_layers"])


def param_count(conf: dict) -> int:
    d, layers = conf["hidden_size"], conf["num_hidden_layers"]
    per_layer = (layer_shared_matmul_params(conf)
                 + conf["num_experts"] * expert_params(conf)
                 + 2 * d                        # attn_norm, mlp_norm
                 + 2 * conf["head_dim"])        # q_norm, k_norm (per head)
    return 2 * conf["vocab_size"] * d + layers * per_layer + d


def decode_step_min_bytes(conf: dict, experts_touched: float,
                          live_rows: float, itemsize: int = 2) -> float:
    """What ONE PASS of ``L`` positions a slot must read at least:
    attention, router and head weights once, the experts its batch
    touched (a layer's mean) in every layer, and the rows its live
    slots hold (committed rows and the open blocks), keys and values."""
    shared = (conf["num_hidden_layers"] * layer_shared_matmul_params(conf)
              + conf["hidden_size"] * conf["vocab_size"])
    experts = (conf["num_hidden_layers"] * experts_touched
               * expert_params(conf))
    return ((shared + experts) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_rows)


def pass_flops(conf: dict, slot_passes: float, rows_seen: float) -> float:
    """Model FLOPs of ``slot_passes`` live (slot, pass) pairs, each of
    ``L`` positions: 2 a matmul weight a position (attention
    projections, router, ``top_k`` experts, the head) and attention
    over the rows the pairs saw (``rows_seen``: a pair's committed rows
    and its block, summed): scores and values, 4 x head_dim a (query
    head, position, row)."""
    L = generation(conf)["block_length"]
    per_token = 2.0 * (
        conf["num_hidden_layers"] * (
            layer_shared_matmul_params(conf)
            + conf["num_experts_per_tok"] * expert_params(conf))
        + conf["hidden_size"] * conf["vocab_size"])
    attn = (4.0 * conf["head_dim"] * conf["num_attention_heads"] * L
            * rows_seen * conf["num_hidden_layers"])
    return slot_passes * L * per_token + attn


def prefill_flops(conf: dict, tokens: float) -> float:
    """Model FLOPs of ``tokens`` real prompt tokens through a block
    engine's prefill: every layer's matmuls, no head (its first tokens
    come out of passes).  The prefill's own attention (under 2% at these
    lengths) is not counted: a share built on this reads that much low,
    never high."""
    return tokens * 2.0 * conf["num_hidden_layers"] * (
        layer_shared_matmul_params(conf)
        + conf["num_experts_per_tok"] * expert_params(conf))


def block_attend_bytes(conf: dict, slot_passes: float, rows_seen: float,
                       layers: int | None = None,
                       itemsize: int = 2) -> float:
    """What the pass programs' attention must move at least, over
    ``layers`` layers (None: the stack): each live pair's rows once a
    pass (keys and values), its block's ``L`` new rows written, ``q``
    read and the result written (``L`` x heads x head_dim each)."""
    L = generation(conf)["block_length"]
    layers = conf["num_hidden_layers"] if layers is None else layers
    row = 2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize
    qo = 2 * L * conf["num_attention_heads"] * conf["head_dim"] * itemsize
    return layers * (rows_seen * row + slot_passes * (L * row + qo))


def expected_experts_touched(conf: dict, tokens: float) -> float:
    """Distinct experts ``tokens`` tokens touch in one layer under a
    uniform router: E (1 - (1 - k / E) ^ tokens)."""
    e, k = conf["num_experts"], conf["num_experts_per_tok"]
    return e * (1.0 - math.pow(1.0 - k / e, tokens))
