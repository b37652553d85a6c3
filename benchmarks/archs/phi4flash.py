"""Phi-4-mini-flash-reasoning (``phi4flash``, the SambaY
"decoder-hybrid-decoder" of arXiv:2507.06607) for the benchmark:
configuration, weights, reference, counts.

One architecture's ``model`` and ``reference`` in one module, as its
five siblings are: ``runners/serve_sambay.py`` registers it as ``model``
and its ``reference`` as ``reference``, and ``runners/serve.py`` then
calls ``transformer_config``, ``init_params`` and ``logits``.
``block_agreement`` is what the cell's ``correct`` also rests on.  No
``edl_tpu`` in the reference.

The reference: plain ``jax.numpy``, float32,
``jax.default_matmul_precision("highest")``, one jitted call a layer.
Its equations, with ``d`` ``hidden_size``, ``n`` layers ``l = 0 .. n -
1``, no positional embedding anywhere:

- every layer ``h = x + Mixer_l(LN(x))``, ``out = h + MLP(LN(h))``;
  ``LN`` LayerNorm with scale and bias, eps ``layer_norm_eps``;
  ``MLP(y) = W2 (up * silu(gate))`` (no bias); logits ``LN_f(x) E^T``
  (the head tied to the embedding);
- the mixer by layer (``mb_per_layer`` 2, ``half = n / 2``): ``l <
  half``: Mamba-1 where ``l`` is even, differential attention over a
  window of ``sliding_window`` where odd; ``l == half``: Mamba-1 that
  also emits the memory ``m``; ``l == half + 1``: differential
  attention, full, causal: the model's only growing cache; above: a
  gated memory unit over ``m`` where ``l`` is even, differential CROSS
  attention over layer ``half + 1``'s keys and values where odd;
- Mamba-1 (``inner = expand x d``, state ``N``, ``R = dt_rank``): ``[x |
  z] = W_in u``; ``x = silu(conv1d_causal(x) + b)``; ``[dt_r | B | C] =
  W_x x``; ``dt = softplus(W_dt dt_r + b_dt)``; ``A = -exp(A_log)``
  ``[inner, N]``; ``S_t = exp(dt_t A) * S_{t-1} + (dt_t x_t) B_t^T``;
  ``y_t = S_t C_t + D * x_t``; ``out = W_out (y * silu(z))``; the
  memory ``m_t = y_t`` (with the ``D`` term, before the gate);
- GMU: ``out = W_out (m_t * silu(W_in u_t))``;
- differential attention (``H`` query heads, ``Hk`` key / value heads
  of ``Dh``; biases on ``Wqkv`` / ``Wq`` and ``W_o``): ``q1[p] =
  q[2p]``, ``q2[p] = q[2p + 1]``; ``k1[r] = k[2r]``, ``k2[r] = k[2r +
  1]``, the same for ``v``; pair ``p`` reads pair ``r = p // 2``;
  ``A_s[p] = softmax(q_s[p] k_s[r]^T / sqrt(Dh))`` under the layer's
  mask (causal; a window layer ``j <= i`` and ``i - j < window``);
  ``O[p] = (A_1[p] - lam A_2[p]) [v1[r] | v2[r]]``; ``O[p] =
  RMSNorm(O[p]; g, 1e-5) (1 - lam0)``; heads ``2p``, ``2p + 1`` take its
  halves; then ``W_o``.  ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 l)``.

Departures from the published code: the gate and up matrices of the MLP
are separate, ``W_in`` of the mixer one matrix ``[x | z]``: fixed
permutations of random weights.  What ``config.json`` has no key for
(the Mamba-1 sizes, differential attention itself, the biases, the
window's edge) is in the configuration file under ``assumed`` and
``assumed_sizes``.

The counts at the end are kept with the benchmark so that no later PR
can move the yardstick.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp

# every key of the published config.json the catalog keeps, and the
# benchmark's own; a key outside both is refused, not ignored
PUBLISHED = {"embd_pdrop", "hidden_act", "hidden_size", "intermediate_size",
             "layer_norm_eps", "max_position_embeddings", "mb_per_layer",
             "model_type", "num_attention_heads", "num_hidden_layers",
             "num_key_value_heads", "resid_pdrop", "sliding_window",
             "tie_word_embeddings", "mlp_bias", "lm_head_bias", "vocab_size"}
OWN = {"source", "architectures", "torch_dtype", "reduced", "reduced_from",
       "assumed", "assumed_sizes", "deployment", "run", "memory",
       "sizing_notes"}
SIZES = {"mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank"}


def _check(conf: dict) -> None:
    unknown = sorted(set(conf) - PUBLISHED - OWN)
    if unknown:
        raise ValueError(f"archs/phi4flash.py maps no key {unknown}: a key "
                         f"it ignored would run another model under this "
                         f"name")
    want = {"model_type": "phi4flash", "hidden_act": "silu",
            "tie_word_embeddings": True, "mlp_bias": False,
            "lm_head_bias": False, "mb_per_layer": 2, "embd_pdrop": 0,
            "resid_pdrop": 0}
    for key, value in want.items():
        if conf[key] != value:
            raise ValueError(f"{key} = {conf[key]!r}: the program's block "
                             f"has {value!r} only")
    if set(conf["assumed_sizes"]) != SIZES:
        raise ValueError(f"assumed_sizes must give {sorted(SIZES)}")
    n = conf["num_hidden_layers"]
    if n % 4 or n < 8:
        raise ValueError(f"{n} layers: the plan needs whole pairs in both "
                         f"halves")
    if (conf["hidden_size"] % conf["num_attention_heads"]
            or conf["num_attention_heads"] != 2 * conf["num_key_value_heads"]
            or conf["num_key_value_heads"] % 2):
        raise ValueError("differential attention pairs heads by stripes")


def layer_kinds(conf: dict) -> tuple:
    """``("mamba" | "window" | "full" | "gmu" | "cross") x n``."""
    n = conf["num_hidden_layers"]
    half = n // 2
    return tuple(
        ("mamba" if l % 2 == 0 else "window") if l < half
        else "mamba" if l == half else "full" if l == half + 1
        else "gmu" if l % 2 == 0 else "cross" for l in range(n))


def _inner(conf: dict) -> int:
    return conf["assumed_sizes"]["mamba_expand"] * conf["hidden_size"]


def _head(conf: dict) -> int:
    return conf["hidden_size"] // conf["num_attention_heads"]


def transformer_config(conf: dict, *, max_len: int, **overrides):
    from edl_tpu.models.transformer import TransformerConfig

    _check(conf)
    types = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
    sizes = conf["assumed_sizes"]
    names = {"mamba": "mamba1", "window": "window", "full": "global",
             "gmu": "gmu", "cross": "cross"}
    kw = dict(vocab_size=conf["vocab_size"],
              num_layers=conf["num_hidden_layers"],
              embed_dim=conf["hidden_size"],
              num_heads=conf["num_attention_heads"],
              num_kv_heads=conf["num_key_value_heads"],
              mlp_dim=conf["intermediate_size"], max_len=max_len,
              tie_embeddings=True,
              dtype=types[conf["run"]["compute_dtype"]],
              attention_impl=conf["run"].get("attention", "auto"),
              norm_eps=float(conf["layer_norm_eps"]), norm="layer",
              attn_bias=True, diff_attn=True,
              attn_window=conf["sliding_window"],
              layer_attn=tuple(names[k] for k in layer_kinds(conf)),
              rope_global=False, rope_window=False,
              m1_inner=_inner(conf), m1_state=sizes["mamba_d_state"],
              m1_conv=sizes["mamba_d_conv"],
              m1_dt_rank=sizes["mamba_dt_rank"],
              ssm_state_dtype=types[conf["run"].get("ssm_state_dtype",
                                                    "float32")])
    kw.update(overrides)
    return TransformerConfig(**kw)


# The embedding rows' spread.  The head is tied to the embedding: with
# unit-normal rows a position's own input token stays its best logit by
# several standard deviations through all the layers, and neither the
# served-token margin nor a greedy answer could see a wrong mixer
# (``archs/granite_moe_hybrid.EMBED_SCALE``).  At this spread the
# residual stream is the layers' doing from the first layer on.
EMBED_SCALE = 0.02


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = True):
    """The parameter tree on the device from the seed, one layer per
    jitted call and cast inside it, ``layer_<i>``.

    The program's own initialisers (lecun-normal matrices; a Mamba-1
    layer's ``dt`` bias the inverse softplus of a log-uniform step in
    [1e-3, 1e-1] and ``A_log = log(uniform[1, 16])``, granite's, so
    that the recurrence is exercised; the four lambda vectors normal
    0.1) with every norm's scale 1 + 0.1 normal and bias 0.1 normal
    (off their trivial values), ``D`` 1 + 0.1 normal, the convolution's
    weights 0.5 normal and bias 0.1 normal, the attention projections'
    biases 0.1 normal, embedding rows ``EMBED_SCALE`` normal."""
    from edl_tpu.models.transformer import Block

    if not split_layers:
        raise ValueError("a stack whose layers differ has no stacked layout")
    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]
    D, V = cfg.embed_dim, cfg.vocab_size

    def cast(path, a, key):
        name, owner = path[-1].key, path[-2].key if len(path) > 1 else ""
        normal = jax.random.normal(key, a.shape, jnp.float32)
        if name in ("scale", "D"):
            a = 1.0 + 0.1 * normal
        elif name == "conv_w":
            a = 0.5 * normal
        elif name == "conv_b" or (name == "bias" and owner != "dt_proj"):
            a = 0.1 * normal
        return a.astype(dt)

    def scaled(tree, key):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [cast(p, a, k) for (p, a), k in zip(leaves, keys)])

    pair = jnp.zeros((1, 8, cfg.kv_heads // 2, 2 * cfg.head_dim), cfg.dtype)
    lent = {"memory": jnp.zeros((1, 8, cfg.m1_inner), cfg.dtype),
            "kv": ("call", pair, pair)}

    @functools.partial(jax.jit, static_argnums=(1,))
    def layer(key, i):
        k1, k2 = jax.random.split(key)
        p = Block(cfg, i).init(k1, jnp.zeros((1, 8, D), cfg.dtype),
                               jnp.zeros((1, 8), jnp.int32), None, None,
                               lent)["params"]
        return scaled(p, k2)

    @jax.jit
    def ends(key):
        k1, k2 = jax.random.split(key)
        return scaled(
            {"tok_embed": {"embedding":
                           EMBED_SCALE * jax.random.normal(k1, (V, D))},
             "final_norm": {"scale": jnp.ones((D,)),
                            "bias": jnp.zeros((D,))}}, k2)

    keys = jax.random.split(jax.random.key(seed % (1 << 31)),
                            cfg.num_layers + 1)
    params = ends(keys[0])
    for i, k in enumerate(keys[1:]):
        params[f"layer_{i}"] = layer(k, i)
    return params


# -- the reference ------------------------------------------------------------
def _f32(x):
    return x.astype(jnp.float32)


def _layernorm(x, p, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * _f32(p["scale"]) + _f32(p["bias"])


def _dense(y, p):
    out = y @ _f32(p["kernel"])
    return out + _f32(p["bias"]) if "bias" in p else out


def mamba_mixer(conf: dict, p, u):
    """The Mamba-1 mixer on ``u [B, L, d]`` (normed input): the plain
    recurrence, one token at a time, from a zero state.  ``(out [B, L,
    d], the memory y [B, L, inner], the state after the last token [B,
    inner, N])``."""
    sizes = conf["assumed_sizes"]
    N, K, R = (sizes["mamba_d_state"], sizes["mamba_d_conv"],
               sizes["mamba_dt_rank"])
    b, l, _ = u.shape
    x, z = jnp.split(_dense(u, p["in_proj"]), 2, axis=-1)
    w = _f32(p["conv_w"])                                      # [K, inner]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(padded[:, i:i + l] * w[i] for i in range(K))
                    + _f32(p["conv_b"]))
    dt_r, bm, cm = jnp.split(_dense(x, p["x_proj"]), [R, R + N], axis=-1)
    dt = jax.nn.softplus(_dense(dt_r, p["dt_proj"]))           # [B, L, inner]
    A = -jnp.exp(_f32(p["A_log"]))                             # [inner, N]

    def step(s, t):
        xt, bt, ct, dtt = t
        s = (s * jnp.exp(dtt[:, :, None] * A)
             + (dtt * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("bdn,bn->bd", s, ct)

    last, y = jax.lax.scan(
        step, jnp.zeros((b, x.shape[-1], N), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, bm, cm, dt)))
    y = jnp.moveaxis(y, 0, 1) + _f32(p["D"]) * x
    return _dense(y * jax.nn.silu(z), p["out_proj"]), y, last


def gmu_mixer(p, u, m):
    return _dense(m * jax.nn.silu(_dense(u, p["in_proj"])), p["out_proj"])


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_attention(conf: dict, p, u, layer: int, kind: str, kv=None):
    """The differential attention mixer of layer ``layer`` on ``u [B,
    L, d]``: ``kind`` "window", "full" or "cross" (``kv``: the keys and
    values ``[B, L, Hk, Dh]`` the full layer projected).  ``(out, (k,
    v))``."""
    H, Hk, Dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 _head(conf))
    b, l, _ = u.shape
    if kind == "cross":
        q = _dense(u, p["attn_q"])
        k, v = kv
    else:
        qkv = _dense(u, p["attn_qkv"])
        q, k, v = jnp.split(qkv, [H * Dh, (H + Hk) * Dh], -1)
        k, v = k.reshape(b, l, Hk, Dh), v.reshape(b, l, Hk, Dh)
    q = q.reshape(b, l, H, Dh)
    i, j = jnp.arange(l)[:, None], jnp.arange(l)[None, :]
    seen = j <= i
    if kind == "window":
        seen = seen & (i - j < conf["sliding_window"])

    def weights(qs, ks):        # [B, L, H/2, Dh] x [B, L, Hk/2, Dh]
        ks = jnp.repeat(ks, 2, axis=2)      # pair p reads pair p // 2
        s = jnp.einsum("bqhd,bkhd->bhqk", qs, ks) / math.sqrt(Dh)
        return jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)

    a1 = weights(q[:, :, 0::2], k[:, :, 0::2])
    a2 = weights(q[:, :, 1::2], k[:, :, 1::2])
    lam0 = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(_f32(p["lambda_q1"]) * _f32(p["lambda_k1"])))
           - jnp.exp(jnp.sum(_f32(p["lambda_q2"]) * _f32(p["lambda_k2"])))
           + lam0)
    both = jnp.repeat(jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], -1),
                      2, axis=2)                          # [B, L, H/2, 2 Dh]
    o = jnp.einsum("bhqk,bkhd->bqhd", a1 - lam * a2, both)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True) + 1e-5)
    o = o * _f32(p["subln"]["scale"]) * (1.0 - lam0)
    return _dense(o.reshape(b, l, H * Dh), p["attn_out"]), (k, v)


def _frozen(conf: dict):
    """The configuration as a hashable static argument."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "sliding_window", "layer_norm_eps")
    return (tuple((k, conf[k]) for k in keys),
            tuple(sorted(conf["assumed_sizes"].items())))


def _thawed(frozen) -> dict:
    return {**dict(frozen[0]), "assumed_sizes": dict(frozen[1])}


@functools.partial(jax.jit, static_argnames=("conf", "kind", "layer"))
def _layer(x, p, memory, kv, *, conf, kind, layer):
    """One layer: ``(x', the mixer's normed input, the mixer's output,
    the memory, the keys and values, a Mamba-1 mixer's last state)``."""
    conf = _thawed(conf)
    eps = float(conf["layer_norm_eps"])
    with jax.default_matmul_precision("highest"):
        u = _layernorm(x, p["attn_norm"], eps)
        state = None
        if kind == "mamba":
            out, memory, state = mamba_mixer(conf, p["ssm"], u)
        elif kind == "gmu":
            out = gmu_mixer(p["gmu"], u, memory)
        else:
            out, made = diff_attention(conf, p, u, layer, kind, kv)
            kv = made if kind == "full" else kv
        h = x + out
        y = _layernorm(h, p["mlp_norm"], eps)
        mlp = (jax.nn.silu(y @ _f32(p["mlp_gate"]["kernel"]))
               * (y @ _f32(p["mlp_in"]["kernel"]))) @ _f32(
                   p["mlp_out"]["kernel"])
        return h + mlp, u, out, memory, kv, state


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, norm, *, eps):
    return _layernorm(x, norm, eps)


@jax.jit
def _head_block(y, rows):
    with jax.default_matmul_precision("highest"):
        return y @ _f32(rows).T


# rows of the vocabulary a block of the head's matmul takes: the float32
# copy of 8192 rows is 84 MB where the whole embedding's is 2 GB, which
# the chip does not have beside an engine
_VOCAB_BLOCK = 8192


def head_logits(x, norm, embedding, eps: float):
    """``LN_f(x) E^T`` [B, L, V] float32 ON THE HOST, the head's matmul
    in blocks of the vocabulary."""
    import numpy as np

    y = _normed(x, norm, eps=eps)
    return np.concatenate([
        np.asarray(_head_block(y, embedding[at:at + _VOCAB_BLOCK]))
        for at in range(0, embedding.shape[0], _VOCAB_BLOCK)], axis=-1)


def forward(conf: dict, params, ids):
    """``(hidden [B, L, d] before the last norm, mixers {layer: (the
    layer's input before its norm, the mixer's normed input, its output,
    the memory and the keys and values it was handed or made, a Mamba-1
    mixer's final state)})``."""
    x = _f32(jnp.take(params["tok_embed"]["embedding"], ids, axis=0))
    memory = kv = None
    mixers = {}
    for i, kind in enumerate(layer_kinds(conf)):
        before = x
        x, u, out, memory, kv, state = _layer(
            x, params[f"layer_{i}"], memory, kv, conf=_frozen(conf),
            kind=kind, layer=i)
        mixers[i] = (before, u, out, memory, kv, state)
    return x, mixers


def reference(conf: dict, params, ids) -> dict:
    """The full forward pass: every layer at every position.  ``logits``
    [B, L, V] float32 ON THE HOST (``head_logits``: at the whole
    vocabulary a probe's logits are most of a gigabyte), ``hidden`` and
    ``mixers`` (``forward``)."""
    x, mixers = forward(conf, params, ids)
    return {"logits": head_logits(x, params["final_norm"],
                                  params["tok_embed"]["embedding"],
                                  float(conf["layer_norm_eps"])),
            "hidden": x, "mixers": mixers}


def logits(conf: dict, params, ids):
    """[B, L, V] float32 logits of the full forward pass."""
    return reference(conf, params, ids)["logits"]


# -- the program's block, for the comparison ---------------------------------
def _lent(cfg, memory, kv):
    """What the program's ``Block`` is handed for the reference's
    ``memory`` and ``kv``: the keys and values paired as its plain
    paths take them."""
    out = {}
    if memory is not None:
        out["memory"] = memory.astype(cfg.dtype)
    if kv is not None:
        b, l = kv[0].shape[:2]
        out["kv"] = ("call", *(a.reshape(b, l, cfg.kv_heads // 2,
                                         2 * cfg.head_dim).astype(cfg.dtype)
                               for a in kv))
    return out


def program_hidden(cfg, params, ids):
    """The PROGRAM's stack over ``ids`` without a cache: ``TransformerLM``
    as it is (full forward, the scan from a zero state, dense attention),
    the hidden rows after the final norm ``[B, L, d]``."""
    from edl_tpu.models.transformer import TransformerLM

    return jax.jit(lambda p, t: TransformerLM(cfg).apply(
        {"params": p}, t, return_hidden=True))(params, ids)


@jax.jit
def _program_head(hidden, embedding):
    """The program's tied head on a few rows (no transposed copy of the
    embedding)."""
    return _f32(jnp.einsum("bld,vd->blv", hidden,
                           embedding.astype(hidden.dtype)))


def program_mixer(cfg, layer_params, x, layer: int, memory=None, kv=None):
    """The PROGRAM's mixer of layer ``layer`` alone: ``Block`` on ``x``
    [B, L, d] (the layer's input BEFORE its norm) handed the reference's
    ``memory`` / ``kv``, and of what it computes the mixer's output
    (``out_proj`` / ``attn_out``)."""
    from edl_tpu.models.transformer import Block

    pos = jnp.broadcast_to(jnp.arange(x.shape[1]), x.shape[:2])
    last = {"mamba1": "out_proj", "gmu": "out_proj"}.get(
        cfg.attn_kind(layer), "attn_out")

    @jax.jit
    def run(p, x, memory, kv):
        lent = {} if memory is None else {"memory": memory}
        if kv is not None:
            lent["kv"] = ("call", *kv)
        _, seen = Block(cfg, layer).apply(
            {"params": p}, x, pos, None, None, lent,
            mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == last)
        return jax.tree.leaves(seen["intermediates"])[0]

    lent = _lent(cfg, memory, kv)
    return _f32(run(layer_params, x.astype(cfg.dtype), lent.get("memory"),
                    lent["kv"][1:] if "kv" in lent else None))


def program_cross_steps(cfg, layer_params, x, layer: int, kv):
    """The PROGRAM's cross layer ``layer`` alone AS A STEP RUNS IT, at
    the first ``CROSS_STEPS`` positions of ``x`` [1, L, d] (the layer's
    input before its norm): a decode-mode ``Block`` takes one row a call
    at position p and reads the lender's slab (the reference's keys and
    values ``kv`` in the slabs' layouts, every row written: the mask, on
    the chip ``decode_attend``'s length, is what stops the read at p).
    Returns the mixer's output [CROSS_STEPS, d] float32."""
    from edl_tpu.models.transformer import Block

    T = 128
    dcfg = dataclasses.replace(cfg, decode=True, max_len=T)
    _, k, v = _lent(cfg, None, kv)["kv"]
    k = jnp.pad(k[:, :T], ((0, 0), (0, T - min(T, k.shape[1])), (0, 0),
                           (0, 0)))
    v = jnp.pad(v[:, :T], ((0, 0), (0, T - min(T, v.shape[1])), (0, 0),
                           (0, 0)))
    slabs = (k.transpose(0, 2, 3, 1), v.transpose(0, 2, 1, 3))

    @jax.jit
    def one(p, row, at, ck, cv):
        _, seen = Block(dcfg, layer).apply(
            {"params": p}, row, at, None, None, {"kv": ("slab", ck, cv)},
            mutable=["intermediates"],
            capture_intermediates=lambda m, _: m.name == "attn_out")
        return seen["intermediates"]["attn_out"]["__call__"][0][0, 0]

    x = x[:1].astype(cfg.dtype)
    return _f32(jnp.stack([
        one(layer_params, x[:, t:t + 1], jnp.full((1, 1), t, jnp.int32),
            *slabs) for t in range(CROSS_STEPS)]))


def program_state(cfg, ssm_params, u, chunk: int):
    """The PROGRAM's Mamba-1 mixer alone THROUGH ITS CACHE on ``u`` [1,
    L, d]: a decode-mode ``Mamba1Mixer`` takes the first ``chunk``
    positions in one call (the scan, the state left in the cache) and
    every later position one token at a time from the cached state (on
    the chip the ``mamba1_step`` kernel), as a slot of the engine does.
    Returns the recurrent state the cache holds at the end, [inner, N]
    float32: hundreds of one-token updates, each kept in
    ``cfg.ssm_state_dtype``."""
    from edl_tpu.models.transformer import Mamba1Mixer

    mixer = Mamba1Mixer(dataclasses.replace(cfg, decode=True))

    @jax.jit
    def run(p, y):
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            jax.eval_shape(mixer.init, jax.random.key(0), y[:, :1])["cache"])
        _, mut = mixer.apply({"params": p, "cache": cache}, y[:, :chunk],
                             mutable=["cache"])

        def one(cache, yt):
            _, mut = mixer.apply({"params": p, "cache": cache}, yt[:, None],
                                 mutable=["cache", "intermediates"])
            return mut["cache"], None

        cache, _ = jax.lax.scan(one, mut["cache"],
                                jnp.moveaxis(y[:, chunk:], 1, 0))
        return _f32(cache["ssm_state"][0]).T

    return run(ssm_params, u[:1].astype(cfg.dtype))


def program_cached(cfg, params, ids, chunk: int, steps: int):
    """The PROGRAM's stack THROUGH ITS CACHE over ``ids`` [1, L], as the
    engine's programs call it: a decode model prefills all but the last
    ``steps`` tokens in chunks of ``chunk`` with the state carried, every
    chunk but the last WITHOUT the tail (``tail=False``), the last with
    the last-position cut (``last_at``), then takes the last ``steps``
    tokens one at a time (on the chip the ``mamba1_step`` and
    ``decode_attend`` kernels).  Returns ``(the logits of the prefill's
    last position and of all those steps but the last [steps, V]
    float32)``: the reference's rows ``L - steps - 1 .. L - 2``."""
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

    @functools.partial(jax.jit, donate_argnums=(1,),
                       static_argnames=("tail",))
    def run(params, cache, tokens, start, tail=True):
        n = tokens.shape[1]
        out, mut = model.apply(
            {"params": params, "cache": cache}, tokens,
            positions=start + jnp.arange(n)[None],
            last_at=jnp.full((1,), n - 1, jnp.int32), tail=tail,
            mutable=["cache", "intermediates"])
        return (out[0, -1] if tail else None), mut["cache"]

    at, out, head = 0, [], L - steps
    while at < L - 1:
        n = min(chunk, head - at) if at < head else 1
        row, cache = run(params, cache, ids[:, at:at + n],
                         jnp.asarray(at, jnp.int32), tail=at + n >= head)
        at += n
        if row is not None:
            out.append(row)
    return jnp.stack(out)


def _rel(diff, want, axes=-1):
    import numpy as np
    return np.asarray(jnp.linalg.norm(diff, axis=axes)
                      / jnp.maximum(jnp.linalg.norm(want, axis=axes), 1e-30)
                      ).reshape(-1)


def slow_channels(ssm_params):
    """The tenth of a Mamba-1 mixer's channels (one at least) whose
    state decays slowest: the smallest ``softplus(b_dt) * exp(A_log)``
    over a channel's states, the decay rate of a step at a zero
    projection."""
    rate = jax.nn.softplus(_f32(ssm_params["dt_proj"]["bias"])) * jnp.exp(
        _f32(ssm_params["A_log"])).min(-1)
    return jnp.argsort(rate)[:max(1, rate.shape[0] // 10)]


CACHE_STEPS = 16
# positions at the START of the probe at which a cross layer alone is
# run one row a call over a slab: a read of the lent rows that is one
# short is a 1,100th of a late position's attention and half of
# position 1's
CROSS_STEPS = 16
# positions of the probe whose logits the program's whole stack is held
# to: every LOGIT_STRIDE-th (at the whole vocabulary all of them are two
# arrays of most of a gigabyte)
LOGIT_STRIDE = 8


def block_agreement(conf: dict, params, ids, ref: dict, *, cfg=None,
                    program_params=None, tag: str = "") -> dict:
    """The program's stack (``cfg`` and ``program_params`` let a
    deliberately wrong variant stand in) against ``reference``'s ``ref``
    on the same ``ids``, and prints:

    ``mixer_error`` [Mamba-1 layers * B * L]: every Mamba-1 mixer ALONE,
    fed the reference's own input to that layer: the norm of (program -
    reference) over the norm of the reference's output, a token.
    ``window_error`` [window layers * B * L] and ``attention_error``
    [(full + cross layers) * B * L]: the same for every differential
    attention layer (a cross layer handed the reference's keys and
    values); the window layers apart, because a window that is one
    short is wrong only past the window's length, in those layers.
    ``gmu_error`` [GMU layers * B * L]: the same for every gated memory
    unit, handed the reference's memory.
    ``state_error`` [Mamba-1 layers * slow channels]: every Mamba-1
    mixer alone THROUGH ITS CACHE (``program_state``: one chunk, then
    every later position a one-token update of the cached state), fed
    the reference's input: the norm of (the state the cache holds at the
    end - the reference recurrence's) over the reference's, a channel,
    for each layer's ``slow_channels``.
    ``logit_error_sigma`` [B * L / LOGIT_STRIDE]: the whole stack
    (``TransformerLM`` without a cache) at the level of logits: at every
    ``LOGIT_STRIDE``-th position the root mean square over the
    vocabulary of (program - reference), in standard deviations of the
    reference's logits there.
    ``cache_error_sigma`` [``CACHE_STEPS``]: the same measure for the
    stack THROUGH ITS CACHE (``program_cached``: chunked prefill with
    state carried and the tail left out, the last-position cut, then
    one-token steps) at the last positions of the probe, against the
    reference's one full pass."""
    import numpy as np

    cfg = cfg or transformer_config(conf, max_len=ids.shape[1], remat=False,
                                    attention_impl="dense")
    program_params = params if program_params is None else program_params
    kinds = layer_kinds(conf)
    want = ref["logits"]
    layers = {i: program_params[f"layer_{i}"] for i in ref["mixers"]}

    def alone(which, start=0):
        return np.concatenate([
            _rel((program_mixer(cfg, layers[i], before, i,
                                memory if kinds[i] == "gmu" else None,
                                kv if kinds[i] == "cross" else None)
                  - out)[:, start:], out[:, start:])
            for i, (before, _, out, memory, kv, _) in ref["mixers"].items()
            if kinds[i] in which])

    mixers, gmus = alone(("mamba",)), alone(("gmu",))
    # the window layers where their window is FULL, if the probe has such
    # positions: a window that is one short is wrong there and nowhere
    # else
    full = conf["sliding_window"] - 1
    windows = alone(("window",), full if ids.shape[1] > full + 1 else 0)
    attention = alone(("full", "cross"))
    chunk = conf["run"]["prefill_chunk"]
    states = np.concatenate([
        _rel((program_state(cfg, layers[i]["ssm"], u, chunk) - last[0])[slow],
             last[0][slow], axes=-1)
        for i, (_, u, _, _, _, last) in ref["mixers"].items()
        if kinds[i] == "mamba"
        for slow in [slow_channels(params[f"layer_{i}"]["ssm"])]])
    hidden = program_hidden(cfg, program_params, ids)[:, ::LOGIT_STRIDE]
    own = np.asarray(_program_head(
        hidden, program_params["tok_embed"]["embedding"]))
    some = want[:, ::LOGIT_STRIDE]
    err = (np.sqrt(np.mean(np.square(own - some), -1))
           / np.std(some, -1)).reshape(-1)
    del own, hidden
    cached = np.asarray(program_cached(cfg, program_params, ids[:1], chunk,
                                       CACHE_STEPS))
    tail = want[0, -CACHE_STEPS - 1:-1]
    cache_err = (np.sqrt(np.mean(np.square(cached - tail), -1))
                 / np.std(tail, -1))
    cross = np.concatenate([
        _rel(program_cross_steps(cfg, layers[i], before, i, kv) - out[0, :CROSS_STEPS],
             out[0, :CROSS_STEPS])
        for i, (before, _, out, _, kv, _) in ref["mixers"].items()
        if kinds[i] == "cross"])
    print(f"[bench] block{tag} ({conf['run']['compute_dtype']}) against the "
          f"float32 reference: Mamba-1 mixers alone, error over norm, median "
          f"{np.median(mixers):.5f} max {mixers.max():.5f} over "
          f"{mixers.size} (token, layer) pairs; differential attention "
          f"layers alone, window median {np.median(windows):.5f} max "
          f"{windows.max():.5f} over {windows.size}, full and cross median "
          f"{np.median(attention):.5f} max "
          f"{attention.max():.5f} over {attention.size}; gated memory units "
          f"alone median {np.median(gmus):.5f} max {gmus.max():.5f} over "
          f"{gmus.size}; the state after one chunk of {chunk} and "
          f"{max(0, ids.shape[1] - chunk)} one-token updates, error over "
          f"norm a slow channel, median {np.median(states):.5f} max "
          f"{states.max():.5f} over {states.size}; logits, median "
          f"{np.median(err):.5f} mean {err.mean():.5f} max {err.max():.5f} "
          f"sigma over {err.size} positions; through the cache (chunks of "
          f"{chunk} without the tail, the last-position cut, then "
          f"{CACHE_STEPS - 1} one-token steps) median "
          f"{np.median(cache_err):.5f} max {cache_err.max():.5f} sigma; "
          f"cross layers alone over a slab, one row a call at the first "
          f"{CROSS_STEPS} positions, error over norm median "
          f"{np.median(cross):.5f} max {cross.max():.5f}", flush=True)
    return {"mixer_error": mixers, "window_error": windows,
            "attention_error": attention,
            "gmu_error": gmus, "state_error": states,
            "logit_error_sigma": err, "cache_error_sigma": cache_err,
            "cross_step_error": cross}


# -- what the algorithms need, from shapes alone ------------------------------
def _count(conf: dict, *kinds: str) -> int:
    return sum(k in kinds for k in layer_kinds(conf))


def mamba_layers(conf: dict) -> int:
    return _count(conf, "mamba")


def kv_readers(conf: dict) -> int:
    """Layers whose one-token step reads the full layer's rows: that
    layer and the cross layers."""
    return _count(conf, "full", "cross")


def mamba_matmul_params(conf: dict) -> int:
    d, di, sizes = conf["hidden_size"], _inner(conf), conf["assumed_sizes"]
    r, n = sizes["mamba_dt_rank"], sizes["mamba_d_state"]
    return d * 2 * di + di * (r + 2 * n) + r * di + di * d


def mamba_params(conf: dict) -> int:
    sizes = conf["assumed_sizes"]
    return mamba_matmul_params(conf) + _inner(conf) * (
        sizes["mamba_d_conv"] + 3 + sizes["mamba_d_state"])


def attention_matmul_params(conf: dict, cross: bool = False) -> int:
    d = conf["hidden_size"]
    kv = 0 if cross else 2 * conf["num_key_value_heads"] * _head(conf) * d
    return 2 * d * d + kv


def attention_params(conf: dict, cross: bool = False) -> int:
    """Matrices, the biases on q (k, v) and the output, four lambda
    vectors and the pair norm's scale."""
    d, dh = conf["hidden_size"], _head(conf)
    kv = 0 if cross else 2 * conf["num_key_value_heads"] * dh
    return attention_matmul_params(conf, cross) + 2 * d + kv + 6 * dh


def gmu_params(conf: dict) -> int:
    return 2 * conf["hidden_size"] * _inner(conf)


def mlp_params(conf: dict) -> int:
    return 3 * conf["hidden_size"] * conf["intermediate_size"]


def layer_matmul_params(conf: dict, kinds=None) -> int:
    """Every matmul weight of the layers of ``kinds`` (None: all)."""
    per = {"mamba": mamba_matmul_params(conf),
           "window": attention_matmul_params(conf),
           "full": attention_matmul_params(conf),
           "cross": attention_matmul_params(conf, cross=True),
           "gmu": gmu_params(conf)}
    return sum(per[k] + mlp_params(conf) for k in layer_kinds(conf)
               if kinds is None or k in kinds)


def param_count(conf: dict) -> int:
    """Every parameter (the head is the embedding)."""
    d, n = conf["hidden_size"], conf["num_hidden_layers"]
    return (conf["vocab_size"] * d + 2 * d + n * (mlp_params(conf) + 4 * d)
            + mamba_layers(conf) * mamba_params(conf)
            + _count(conf, "window", "full") * attention_params(conf)
            + _count(conf, "cross") * attention_params(conf, cross=True)
            + _count(conf, "gmu") * gmu_params(conf))


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    """Of the full layer: the only cache that grows with the context."""
    return 2 * conf["num_key_value_heads"] * _head(conf) * itemsize


def state_bytes_per_slot(conf: dict, itemsize: int = 2,
                         state_itemsize: int = 4) -> int:
    sizes = conf["assumed_sizes"]
    return mamba_layers(conf) * _inner(conf) * (
        sizes["mamba_d_state"] * state_itemsize
        + (sizes["mamba_d_conv"] - 1) * itemsize)


def ssm_step_min(conf: dict, pairs: float, itemsize: int = 2
                 ) -> tuple[float, float]:
    """``(flops, bytes)`` of the ``mamba1_step`` kernel for ``pairs``
    live (slot, token step, layer) states: the state read once and
    written once (float32), the update (a decay, a product, an add) and
    the readout at 2 FLOPs a multiply-add each a state element, and the
    step's own rows as the kernel takes them in float32: ``dt`` and
    ``dt * x`` in, B and C in, y out.  (``A`` stays in VMEM over the
    slots of a call.)"""
    n = conf["assumed_sizes"]["mamba_d_state"]
    state = _inner(conf) * n
    rows = 4 * (3 * _inner(conf) + 2 * n)
    return pairs * 6.0 * state, pairs * (2.0 * 4 * state + rows)


def window_layers(conf: dict) -> int:
    return _count(conf, "window")


def window_attention_min(conf: dict, positions: float,
                         itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of the window layers' one-token append and
    attend, all window layers together, for live (slot, token step)
    pairs whose windows held ``positions`` keys in all (``sum
    min(length, sliding_window)``, of ONE layer), as
    ``archs/exaone_moe.window_attention_min`` counts: scores and
    weighted values at the published head size, 2 FLOPs a multiply-add,
    every query head against its window; the window's keys and values
    read once."""
    flops = 2 * 2.0 * conf["num_attention_heads"] * _head(conf) * positions
    return (window_layers(conf) * flops,
            window_layers(conf) * kv_bytes_per_token(conf, itemsize)
            * positions)


def shared_kv_attend_min(conf: dict, rows: float, itemsize: int = 2
                         ) -> tuple[float, float]:
    """``(flops, bytes)`` of the ``decode_attend`` kernels of the full
    layer and the cross layers for ``rows`` (query row, reading layer,
    visible position) reads of the full layer's cache: each position's
    keys and values once a read, two matmuls over them for every query
    head at the published head size.  The query in and the output out
    (a 5,120th of a 1,000-row read) are left out."""
    return (rows * 2.0 * 2 * conf["num_attention_heads"] * _head(conf),
            rows * kv_bytes_per_token(conf, itemsize))


def step_flops(conf: dict, tokens: float, kv_rows: float,
               window_rows: float) -> float:
    """Model FLOPs of ``tokens`` token steps (one token through every
    layer and the head): 2 a matmul weight, the recurrence (6 a state
    element a Mamba-1 layer), and attention's two matmuls over
    ``kv_rows`` (token, reading layer, visible row of the full layer's
    cache) and ``window_rows`` (token, window layer, visible row)
    pairs."""
    sizes = conf["assumed_sizes"]
    d = conf["hidden_size"]
    weights = layer_matmul_params(conf) + d * conf["vocab_size"]
    scan = mamba_layers(conf) * 6.0 * _inner(conf) * sizes["mamba_d_state"]
    pair = 2.0 * 2 * conf["num_attention_heads"] * _head(conf)
    return tokens * (2.0 * weights + scan) + (kv_rows + window_rows) * pair


def prefill_flops(conf: dict, tokens: float, last_rows: float,
                  kv_pairs: float, window_pairs: float) -> float:
    """Model FLOPs of multi-token programs over ``tokens`` real tokens
    under the last-position cut: the layers below the tail at every
    token, the tail and the head at ``last_rows`` rows alone, attention
    over ``kv_pairs`` (query, visible row) pairs of the full layer (a
    tail row's ``kv_readers - 1`` more reads of its rows are in
    ``kv_pairs`` as the caller counts them) and ``window_pairs`` of the
    window layers."""
    sizes = conf["assumed_sizes"]
    d = conf["hidden_size"]
    below = layer_matmul_params(conf, ("mamba", "window", "full"))
    tail = layer_matmul_params(conf, ("gmu", "cross")) + d * conf["vocab_size"]
    scan = mamba_layers(conf) * 6.0 * _inner(conf) * sizes["mamba_d_state"]
    pair = 2.0 * 2 * conf["num_attention_heads"] * _head(conf)
    return (tokens * (2.0 * below + scan) + last_rows * 2.0 * tail
            + (kv_pairs + window_pairs) * pair)
