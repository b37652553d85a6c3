"""Operations and bytes the algorithms need, from shapes alone.

Kept with the benchmark so that no later PR can move the yardstick.
``conf`` is a configuration file's dict (published key names).
Recomputed operations (rematerialisation, flash-style recompute of the
scores in the backward pass) are never counted.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown device is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to "
                       f"benchmarks/peaks.json with its source")
    return table[device_kind]


def layer_matmul_params(conf: dict) -> int:
    d, m = conf["hidden_size"], conf["intermediate_size"]
    h, hk, dh = (conf["num_attention_heads"], conf["num_key_value_heads"],
                 conf["head_dim"])
    return d * (h + 2 * hk) * dh + h * dh * d + 3 * d * m


def matmul_params(conf: dict) -> int:
    """Parameters that take part in a matmul for every token: the
    layers and the head; the embedding table is a lookup."""
    head = conf["hidden_size"] * conf["vocab_size"]
    return conf["num_hidden_layers"] * layer_matmul_params(conf) + head


def param_count(conf: dict) -> int:
    d = conf["hidden_size"]
    tied = conf.get("tie_word_embeddings", False)
    return (conf["vocab_size"] * d * (1 if tied else 2)
            + conf["num_hidden_layers"] * (layer_matmul_params(conf) + 2 * d)
            + d)


def train_flops_per_token(conf: dict, seq: int) -> float:
    """Forward + backward: 6 per matmul parameter (the PaLM appendix's
    accounting, with the GQA projection widths; obs/flops.py's copy
    assumes MHA) plus causal attention, 6 * layers * seq * hidden."""
    attn = 6 * conf["num_hidden_layers"] * seq * (
        conf["num_attention_heads"] * conf["head_dim"])
    return float(6 * matmul_params(conf) + attn)


def attention_train_flops(conf: dict, seq: int, sequences: int,
                          layers: int | None = None) -> float:
    """Causal self-attention forward + backward for ``sequences``
    sequences through ``layers`` layers: two matmuls forward and four
    backward (dV, dP, dQ, dK), 2*S*S*Dh*H each, halved by the mask."""
    layers = conf["num_hidden_layers"] if layers is None else layers
    per = 2.0 * seq * seq * conf["head_dim"] * conf["num_attention_heads"]
    return 6 * per * 0.5 * sequences * layers


def kv_bytes_per_token(conf: dict, itemsize: int = 2) -> int:
    return (2 * conf["num_key_value_heads"] * conf["head_dim"] * itemsize
            * conf["num_hidden_layers"])


def decode_step_min_bytes(conf: dict, live_tokens: float,
                          itemsize: int = 2) -> float:
    """What one decode step (one new token in every active slot) must
    read at least: every matmul weight once, and the keys and values of
    the tokens that are live.  The engine reads whole slabs; that is its
    cost, not the algorithm's."""
    return (matmul_params(conf) * itemsize
            + kv_bytes_per_token(conf, itemsize) * live_tokens)


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_f = flops / peak["bf16_flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
