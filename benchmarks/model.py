"""From a configuration file to the program's own model objects.

The file under ``configs/`` keeps the published key names; this is the
one place that maps them onto ``TransformerConfig``.  Weights come from
``--seed`` in one jitted call on the device, in the type the file's
``run.param_dtype`` states.
"""

from __future__ import annotations


def transformer_config(conf: dict, *, max_len: int, **overrides):
    import jax.numpy as jnp

    from edl_tpu.models.transformer import TransformerConfig

    heads, head_dim = conf["num_attention_heads"], conf["head_dim"]
    if heads * head_dim != conf["hidden_size"]:
        # the program derives head_dim as embed_dim // num_heads
        raise ValueError("the program needs heads * head_dim == hidden_size")
    if conf["hidden_act"] != "silu" or conf.get("sliding_window"):
        raise ValueError("the program's Block is gated SiLU, full attention")
    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
        conf["run"]["compute_dtype"]]
    kw = dict(vocab_size=conf["vocab_size"],
              num_layers=conf["num_hidden_layers"],
              embed_dim=conf["hidden_size"], num_heads=heads,
              num_kv_heads=conf["num_key_value_heads"],
              mlp_dim=conf["intermediate_size"], max_len=max_len,
              rope_theta=float(conf["rope_theta"]),
              tie_embeddings=bool(conf["tie_word_embeddings"]), dtype=dtype,
              attention_impl=conf["run"].get("attention", "auto"))
    kw.update(overrides)
    return TransformerConfig(**kw)


def init_params(cfg, seed: int, param_dtype: str, split_layers: bool = False):
    """The parameter tree, made on the device from the seed in one
    jitted call and cast there: stacked ``layers`` (the training
    layout) or, with ``split_layers``, ``layer_<i>`` (what the serving
    engine unrolls a trained tree into; made split here so that no
    second copy of the layers ever lives on the chip)."""
    import jax
    import jax.numpy as jnp

    from edl_tpu.models.transformer import TransformerLM

    dt = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[param_dtype]

    def make(key):
        p = TransformerLM(cfg).init(key, jnp.zeros((1, 8), jnp.int32))
        p = jax.tree.map(lambda a: a.astype(dt), p["params"])
        if split_layers:
            stacked = p.pop("layers")
            for i in range(cfg.num_layers):
                p[f"layer_{i}"] = jax.tree.map(lambda a: a[i], stacked)
        return p

    return jax.jit(make)(jax.random.key(seed % (1 << 31)))
