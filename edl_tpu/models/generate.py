"""Autoregressive generation for :class:`TransformerLM` with a KV cache.

The reference serves classification-style teachers (Paddle Serving
forward passes); an LM framework also needs decode-side inference.
This is the jit-native version: one prefill pass writes the prompt's
keys/values into per-layer caches (``cfg.decode=True`` attention,
transformer.Block._decode_attention), then a ``lax.scan`` emits one
token per step — O(1) attention work per token instead of re-running
the full prefix, static shapes throughout.

Sampling: greedy (``temperature=0``), temperature softmax, optional
top-k truncation and/or top-p nucleus.  Deterministic under a fixed
``rng``.
"""

from __future__ import annotations

import collections
import dataclasses

import jax
import jax.numpy as jnp

from edl_tpu.models.transformer import TransformerConfig, TransformerLM


def _split_layer_params(params, num_layers: int):
    """Trained params stack the decoder layers (nn.scan, leading dim =
    num_layers); the decode model unrolls them into per-layer modules
    (layer_0..layer_N-1) so every layer's KV cache is a separate buffer
    XLA can update in place inside the generation loop."""
    if "layers" not in params:      # already split
        return params
    stacked = params["layers"]
    out = {k: v for k, v in params.items() if k != "layers"}
    for i in range(num_layers):
        out[f"layer_{i}"] = jax.tree.map(lambda a: a[i], stacked)
    return out


def _split_rules():
    """LOGICAL_RULES rewritten for the SPLIT (per-layer unrolled) param
    tree: ``layers/...`` paths become ``layer_<i>/...`` and lose the
    leading ``layers`` stacking axis."""
    from edl_tpu.models.transformer import LOGICAL_RULES

    out = []
    for pat, axes in LOGICAL_RULES:
        if pat.startswith("layers/"):
            out.append((r"layer_\d+/" + pat[len("layers/"):], axes[1:]))
        else:
            out.append((pat, axes))
    return out


def shard_split_params(params, mesh, num_layers: int, rules=None):
    """Split stacked layer params and shard them over ``mesh`` by their
    logical axes (megatron tp on heads/mlp/vocab under the default
    rules) — the serving-side twin of ElasticTrainer.create_state's
    sharded init.  ``params`` may be stacked (training layout) or
    already split.  Returns the device-put split tree; jitting
    generate()/the engine step over it makes XLA insert the tp
    collectives (computation follows data) — the multi-chip serving
    path for models bigger than one chip's HBM (the reference's
    teacher regime: a ResNeXt101 spanning its GPU,
    /root/reference/README.md:51-64)."""
    from edl_tpu.parallel.sharding import device_put_by_logical

    split = _split_layer_params(params, num_layers)
    return device_put_by_logical(split, _split_rules(), mesh, rules)


def sample_logits(logits, key, *, temperature: float = 1.0, top_k: int = 0,
                  top_p: float = 0.0, top_k_recall: float = 0.95):
    """[B, V] logits -> [B] sampled token ids (the one sampling recipe
    shared by generate() and the continuous-batching engine — the two
    serving paths must never diverge).  Greedy at ``temperature<=0``;
    else temperature softmax, optional top-k truncation (TPU-native
    ``approx_max_k`` threshold at ``top_k_recall``) then top-p nucleus."""
    import jax
    import jax.numpy as jnp

    if temperature <= 0:
        return logits.argmax(-1).astype(jnp.int32)
    scaled = logits / temperature
    if top_k:
        kth = jax.lax.approx_max_k(
            scaled, top_k, recall_target=top_k_recall)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if top_p and top_p < 1.0:
        # nucleus: drop tokens outside the smallest prefix (by
        # descending probability) whose cumulative mass reaches p;
        # the top token always survives (cumsum-exclusive < p)
        sorted_ = jnp.sort(scaled, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_, axis=-1)
        csum = jnp.cumsum(probs, axis=-1) - probs
        kept = jnp.where(csum < top_p, sorted_, jnp.inf)
        cutoff = jnp.min(kept, axis=-1, keepdims=True)
        scaled = jnp.where(scaled < cutoff, -jnp.inf, scaled)
    return jax.random.categorical(key, scaled).astype(jnp.int32)


def generate(cfg: TransformerConfig, params, prompt, max_new_tokens: int,
             *, rng=None, temperature: float = 1.0, top_k: int = 0,
             top_p: float = 0.0, top_k_recall: float = 0.95,
             return_drops: bool = False):
    """Sample ``[B, max_new_tokens]`` continuations of ``prompt [B, P]``.

    ``cfg`` is the TRAINING config (``decode`` is overridden here);
    ``params`` the trained parameters.  Call under jit for real use —
    everything inside is jit-compatible.

    Sampling: greedy (``temperature=0``), else temperature softmax
    optionally truncated by ``top_k`` (keep the k best logits) and/or
    ``top_p`` in (0, 1] (nucleus: keep the smallest set of tokens whose
    probability mass reaches p; applied after top_k).

    ``top_k_recall``: the top-k threshold uses the TPU-native
    ``lax.approx_max_k`` at this per-bucket recall (the sort-based
    exact top-k profiled 1.6 ms/step at [64, 32000] — dwarfing the
    attention itself).  0.95 is statistically invisible under stochastic
    sampling (a missed candidate is replaced by a near-tied logit);
    pass 1.0 for the exact threshold at ~0.5 ms/step extra.

    ``return_drops=True`` additionally returns the MoE prefill's
    capacity-overflow count (scalar i32; always 0 for dense configs and
    for the decode steps, whose per-token gather cannot drop) —
    ``(tokens, drops)``.  A serving path with an under-provisioned
    ``capacity_factor`` silently degrades on long prompts; this makes
    it measurable (ops/moe.py ``moe_drops``)."""
    if prompt.ndim != 2:
        raise ValueError(f"prompt must be [B, P], got {prompt.shape}")
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
    B, P = prompt.shape
    if P + max_new_tokens > cfg.max_len:
        raise ValueError(
            f"prompt {P} + new {max_new_tokens} exceeds max_len "
            f"{cfg.max_len} (the KV cache size)")
    if not 0.0 <= top_p <= 1.0:
        raise ValueError(f"top_p must be in [0, 1], got {top_p}")
    # Capacity-path MoE configs decode with per-token expert gather
    # (ops/moe.py decode=True): no capacity machinery, so output
    # matches the training forward exactly whenever training capacity
    # dropped nothing (ample capacity_factor); when training did drop
    # overflow tokens, decode is the drop-free ideal rather than a
    # replica.  Dropless configs (moe_capacity <= 0) run one path for
    # prefill and decode and never drop.
    #
    # The KV cache is sized to THIS request (P + new, padded to the
    # 128-lane tile), not cfg.max_len: every decode step streams the
    # whole cache through the two attention matmuls, so a 1024-long
    # cache for a 256-long generation costs 4× the HBM traffic of a
    # right-sized one (profiled: the cache reads are the decode-loop
    # floor once sampling is fast).  RoPE uses absolute positions, so
    # shrinking the cache does not move any embedding.
    cache_len = min(cfg.max_len, -(-(P + max_new_tokens) // 128) * 128)
    # cfg.mesh stays: "dense" never reads it, and a model that has one
    # (tp-sharded serving) keeps its decode steps on the einsum path
    dcfg = dataclasses.replace(cfg, decode=True, attention_impl="dense",
                               max_len=cache_len)
    model = TransformerLM(dcfg)
    params = _split_layer_params(params, cfg.num_layers)
    rng = jax.random.key(0) if rng is None else rng

    # zeroed caches at [B, max_len], sized WITHOUT materialising params
    # (eval_shape traces init; only the cache skeleton is realised)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), prompt[:, :1],
                           positions=jnp.zeros((B, 1), jnp.int32)))
    cache = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                         shapes["cache"])

    # prefill: write the prompt's k/v, take the next-token logits
    # (intermediates carries the MoE capacity-overflow count)
    logits, mut = model.apply(
        {"params": params, "cache": cache}, prompt,
        positions=jnp.broadcast_to(jnp.arange(P), (B, P)),
        mutable=["cache", "intermediates"])
    cache = mut["cache"]
    drops = _sum_drops(mut.get("intermediates"))

    def sample(logits_1, key):
        return sample_logits(logits_1, key, temperature=temperature,
                             top_k=top_k, top_p=top_p,
                             top_k_recall=top_k_recall)

    rng, k0 = jax.random.split(rng)
    first = sample(logits[:, -1], k0)

    def step(carry, _):
        cache, tok, pos, key = carry
        key, sk = jax.random.split(key)
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            positions=jnp.full((B, 1), pos, jnp.int32), mutable=["cache"])
        nxt = sample(logits[:, -1], sk)
        return (mut["cache"], nxt, pos + 1, key), tok

    (_, last, _, _), toks = jax.lax.scan(
        step, (cache, first, jnp.asarray(P, jnp.int32), rng), None,
        length=max_new_tokens - 1)    # length 0 is fine for 1 new token
    out = jnp.concatenate([toks.T, last[:, None]], axis=1)
    return (out, drops) if return_drops else out


def sown(intermediates) -> dict:
    """``{sown name: its leaves}`` of what a program's layers sowed
    into ``intermediates``, a leaf a layer call."""
    out = collections.defaultdict(list)
    for path, leaf in jax.tree_util.tree_leaves_with_path(
            intermediates or {}):
        out[[k.key for k in path if hasattr(k, "key")][-1]].append(leaf)
    return out


def _sum_drops(intermediates) -> "jax.Array":
    """Total ``moe_drops`` over all layers (0 for dense configs)."""
    return sum((jnp.asarray(leaf, jnp.int32).sum()
                for leaf in sown(intermediates).get("moe_drops", ())),
               jnp.zeros((), jnp.int32))


def sown_layout(*intermediates) -> tuple:
    """``((sown name, width), ...)`` of everything sown in any of
    ``intermediates`` (shapes suffice), by name."""
    return tuple(sorted({
        name: leaves[0].shape[-1] if leaves[0].ndim else 1
        for inter in intermediates
        for name, leaves in sown(inter).items()}.items()))


def sown_vector(intermediates, layout: tuple) -> "jax.Array":
    """What a program's layers sowed, for the host, as ONE vector: for
    each ``(name, width)`` of ``layout`` the ``width`` entries summed
    over the layer calls, then how many calls sowed it; float32 (every
    entry is a count or a small ratio, exact at serving sizes), zeros
    for a name this program did not sow, zero-width for an empty layout
    (the serving engine's programs return it;
    ``serving/model_counters.py`` books it)."""
    got, parts = sown(intermediates), [jnp.zeros((0,), jnp.float32)]
    if set(got) - {name for name, _ in layout}:
        raise ValueError(
            f"a program's layers sow {sorted(got)}; the layout found at "
            f"construction holds {layout}")
    for name, width in layout:
        rows = [jnp.asarray(leaf, jnp.float32).reshape(-1, width)
                for leaf in got.get(name, ())]
        parts += [sum((r.sum(0) for r in rows),
                      jnp.zeros((width,), jnp.float32)),
                  jnp.full((1,), sum(len(r) for r in rows), jnp.float32)]
    return jnp.concatenate(parts)
