"""The plain reference against the program's own model in float32 at a
toy width: the same weights give the same logits and the same loss,
with the attention computed whole and in blocks of query rows."""
import jax.numpy as jnp
import numpy as np
import pytest

import model
import reference

CONF = {"vocab_size": 97, "num_hidden_layers": 2, "hidden_size": 64,
        "num_attention_heads": 4, "head_dim": 16, "num_key_value_heads": 2,
        "intermediate_size": 96, "rope_theta": 1e6,
        "tie_word_embeddings": False, "hidden_act": "silu",
        "run": {"compute_dtype": "float32"}}


@pytest.mark.parametrize("q_block", [8, 512])
@pytest.mark.parametrize("split", [False, True])
def test_reference_equals_the_program_in_float32(q_block, split, monkeypatch):
    from edl_tpu.models.transformer import TransformerLM, lm_loss
    monkeypatch.setattr(reference, "Q_BLOCK", q_block)
    cfg = model.transformer_config(CONF, max_len=64, attention_impl="dense",
                                   remat=False)
    params = model.init_params(cfg, 5, "float32")
    ids = np.random.default_rng(0).integers(1, 97, (2, 33))
    want = TransformerLM(cfg).apply({"params": params},
                                    jnp.asarray(ids[:, :-1]))
    tree = (model.init_params(cfg, 5, "float32", split_layers=True)
            if split else params)
    got = reference.logits(CONF, tree, jnp.asarray(ids[:, :-1]))
    assert float(jnp.abs(want - got).max()) < 2e-5
    assert reference.loss(CONF, tree, ids) == pytest.approx(
        float(lm_loss(want, jnp.asarray(ids[:, 1:]))), abs=1e-5)
