"""The lowered text of a decode model's step and chunk calls, hashed:
how a PR that adds configuration fields shows that a configuration
which leaves them off compiles the programs it did.

    PYTHONPATH=<a checkout> JAX_PLATFORMS=cpu python -m tests.helpers.lowered

prints ``{name: {width: hash}}`` of the toy serve configurations under
THAT checkout's ``edl_tpu``, and whether they are ``PARENT``'s.  Run by
hand (not a test: a change to the decode model, a JAX upgrade or a new
toy configuration moves the text with no fault behind it); the PR that
runs it records the parent's hashes here and the result in PERF.md."""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp

# under commit 90112f3 (PR 52's parent): the step (1) and chunk (8)
# calls of the toy serve configurations, none of which sets a field PR
# 52 adds (``attn_gate``, ``kda_neg_eigval``) or has dense scores that
# do not fit
PARENT = {
    "dense_gqa": {"1": "96f7272487c533b9", "8": "08ff7e3317a8c361"},
    "exaone_window_held": {"1": "de4e50d1ae432ffc", "8": "8be05426f2b9b54b"},
    "granite_ssm": {"1": "68e733c55a24ffe9", "8": "adff3c5c92cf93ca"},
    "kimi_kda_latent": {"1": "1b608eab4f3e4d21", "8": "736037d46af7233b"},
}


def toys() -> dict:
    from edl_tpu.models.transformer import TransformerConfig
    from tests.test_engine_model_counters import CONFIGS
    gqa = TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                            num_heads=4, num_kv_heads=2, mlp_dim=64,
                            max_len=96, remat=False, dtype=jnp.float32)
    return {"dense_gqa": gqa, **{k: CONFIGS[k] for k in (
        "exaone_window_held", "granite_ssm", "kimi_kda_latent")}}


def lowered_hash(cfg, width: int) -> str:
    """Two lanes x ``width`` positions of the decode model over a fresh
    cache, as the engine's step (1) and chunk (8) programs call it."""
    from edl_tpu.models.transformer import TransformerLM

    model = TransformerLM(dataclasses.replace(cfg, decode=True,
                                              attention_impl="dense"))
    ids = jnp.zeros((2, width), jnp.int32)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), ids[:, :1], positions=ids[:, :1]))

    def call(variables, ids):
        return model.apply(variables, ids, positions=ids,
                           mutable=["cache", "intermediates"])

    text = jax.jit(call).lower(shapes, ids).as_text()
    return hashlib.sha256(text.encode()).hexdigest()[:16]


if __name__ == "__main__":
    found = {name: {str(w): lowered_hash(cfg, w) for w in (1, 8)}
             for name, cfg in toys().items()}
    print(json.dumps(found))
    print("the parent's, hash for hash:", found == PARENT)
