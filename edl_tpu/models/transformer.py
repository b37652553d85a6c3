"""Flagship transformer LM — the beyond-parity workload.

The reference tops out at data-parallel ResNet (SURVEY.md §5
"Long-context: absent").  This decoder-only LM is designed for the
mesh from day one:

- logical axes on every weight (megatron TP on ``tp``, zero-style
  ``fsdp``, sequence shards on ``sp``) — ``LOGICAL_RULES`` feeds
  ``ElasticTrainer.create_state``;
- training activations pinned to the batch axes by logical names
  (``_pin``: the residual stream ``("batch", "seq", None)``, projections
  ``("batch", "seq", "heads" | "mlp")``): XLA still places the
  collectives, but between chips it moves weights, never a whole batch;
- ``lax.scan`` over stacked layer params (one compile for N layers) with
  optional ``jax.checkpoint`` rematerialisation;
- attention dispatch from :mod:`edl_tpu.ops.attention` (XLA dense /
  pallas flash / ring sequence-parallel);
- RoPE positions, RMSNorm, bf16 compute / f32 params.
"""

from __future__ import annotations

import contextlib
import functools
import math
from dataclasses import dataclass, field
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from edl_tpu.ops import decode_attention, kda, latent_attention, mamba1, ssm
from edl_tpu.ops.attention import (
    SPLASH_RESIDUALS, dot_product_attention, splash_partials_bytes,
)
from edl_tpu.parallel.sharding import logical_constraint, logical_sharding

# param-path regex → logical axes (ElasticTrainer.create_state consumes)
LOGICAL_RULES = [
    (r"tok_embed/embedding", ("vocab", "embed")),
    (r"layers/attn_qkv/kernel", ("layers", "embed", "heads")),
    (r"layers/attn_out/kernel", ("layers", "heads", "embed")),
    (r"layers/mlp_in/kernel", ("layers", "embed", "mlp")),
    (r"layers/mlp_gate/kernel", ("layers", "embed", "mlp")),
    (r"layers/mlp_out/kernel", ("layers", "mlp", "embed")),
    (r"layers/moe/gate", ("layers", "embed", None)),
    (r"layers/moe/w_in", ("layers", "expert", "embed", "expert_mlp")),
    (r"layers/moe/w_out", ("layers", "expert", "expert_mlp", "embed")),
    (r"layers/moe/w_gate", ("layers", "expert", "embed", "expert_mlp")),
    (r"layers/moe/gate_bias", ("layers", None)),
    (r"layers/moe/shared_(gate|in)/kernel", ("layers", "embed", "mlp")),
    (r"layers/moe/shared_out/kernel", ("layers", "mlp", "embed")),
    # over the whole q / k projection; replicated like the other norms
    (r"layers/q_norm/scale", ("layers", "norm")),
    (r"layers/k_norm/scale", ("layers", "norm")),
    (r"layers/.*norm/scale", ("layers", "norm")),
    (r"final_norm/scale", ("norm",)),
    (r"lm_head/kernel", ("embed", "vocab")),
]


@dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    num_layers: int = 12
    embed_dim: int = 768
    # 6 × 128-wide heads, not 12 × 64: the MXU is a 128×128 systolic
    # array, so 128-wide attention contractions run the pallas kernels
    # at full tile width (profiled 5× faster fwd+bwd than head_dim 64
    # at the flagship shape; same FLOPs/params either way)
    num_heads: int = 6
    mlp_dim: int = 3072
    max_len: int = 2048
    # grouped-query attention: number of K/V heads (0 = num_heads, i.e.
    # plain MHA).  Serving-side win: the decode KV cache shrinks by
    # num_heads/num_kv_heads — every decode step streams the whole
    # cache, so GQA directly multiplies decode throughput and slots
    # per chip (models/generate.py, serving/engine.py need no changes:
    # cache shapes follow the config).
    num_kv_heads: int = 0
    dtype: Any = jnp.bfloat16
    attention_impl: str = "auto"      # auto | dense | splash | flash | ring
    # ring needs it; splash shards by it; a decode model that has one
    # keeps its slabs on the einsum path (ops/decode_attention.applies)
    mesh: Any = None
    remat: bool = True
    # lax.scan over stacked layer params (one compile for N layers) vs
    # unrolled python loop.  Scan trades ~12% step time for compile
    # time: every per-layer residual is COPIED into a stacked buffer
    # (dynamic_update_slice) on the forward and sliced back out on the
    # backward — profiled ~11 ms/step at the flagship config — where
    # unrolled layers keep residuals as their natural buffers.  Params
    # stay stacked [num_layers, ...] either way (checkpoint-compatible).
    scan_layers: bool = True
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    # mixture-of-experts MLP (ops/moe.py): 0 = dense MLP; > 0 routes
    # every block's FFN over this many experts (shard over ``ep``)
    moe_experts: int = 0
    moe_top_k: int = 2
    # <= 0 selects the dropless path (sort + grouped matmuls, one path
    # for training, prefill and decode); > 0 is the GShard capacity
    # factor of the dense-dispatch path (ops/moe.py says which runs when)
    moe_capacity: float = 1.25
    # gated experts (w_gate, w_in, w_out: silu(x w_gate) * (x w_in)),
    # and whether the top-k gates are renormalised to sum 1
    moe_gated: bool = False
    moe_norm_topk: bool = True
    # RMSNorm of the whole q and k projections before the split into
    # heads (OLMoE's q_norm / k_norm), and every RMSNorm's epsilon
    qk_norm: bool = False
    norm_eps: float = 1e-6
    # autoregressive decoding: attention reads/writes a per-layer KV
    # cache ("cache" collection) instead of recomputing the prefix
    # (models/generate.py drives this)
    decode: bool = False
    # multi-token decode calls (L > 1) write K/V at PER-EXAMPLE cache
    # indices (an XLA scatter) instead of one batch-uniform
    # dynamic_update_slice.  Off by default: prefill always writes from
    # index 0 of a fresh cache, where the contiguous DUS is the faster
    # path.  The serving engine's speculative-decode verify model flips
    # this on — verified slots sit at heterogeneous positions
    # (serving/engine.py) — with out-of-bounds rows DROPPED, never
    # clamped (a clamp would smear the last position over live state).
    decode_scatter: bool = False
    # generation by diffusion over blocks: position i sees j iff
    # j // block_length <= i // block_length (block-causal; 0 = causal,
    # every program what it was).  The mask is over ABSOLUTE positions in
    # every forward; with ``decode_scatter`` a decode model's call of
    # 2 * block_length positions is a PASS over a slot's blocks
    # (``Block._pass_attention``): the finished block a slot commits, if
    # any, at the slot's index, and its open block at the index or behind
    # the committed one; every query reads up to its own block's end, the
    # logits are the open block's, and the index moves only where a block
    # commits (serving/engine.py drives it); a call of block_length
    # positions is a pass of the open block alone
    block_length: int = 0
    # the generation loop a block model is published with, beside its
    # block length (the model does not read them; the serving engine
    # does): denoising steps a block (0: block_length, one position a
    # pass; a denoise pass unmasks ceil(block_length / steps) positions),
    # how a pass chooses them ("static": that many, the surest;
    # "dynamic": every one surer than block_threshold and at least the
    # surest), and the vocabulary's row a masked position is fed as
    block_steps: int = 0
    block_remasking: str = "static"
    block_threshold: float = 0.9
    block_mask_id: int = 0
    # -- layers that differ (a published ``layer_types`` /
    # ``mlp_layer_types`` plan).  Every field below is off by default,
    # and a configuration that leaves them off builds the modules and
    # compiles the programs it always did.
    # a head size that is not embed_dim // num_heads (``head_dim``)
    attn_head_dim: int = 0
    # sliding-window attention (``sliding_window``): a "window" layer's
    # position i sees j with j <= i and i - j < attn_window (itself
    # included).  layer_attn[i] is "window" or "global"; () = every
    # layer "window" when attn_window is set, else "global"
    attn_window: int = 0
    layer_attn: tuple = ()
    # False: the global layers of a plan do not rotate q and k (no
    # positional embedding there); rope_window says the same of the
    # window layers (both False: no positional embedding anywhere)
    rope_global: bool = True
    rope_window: bool = True
    # an output gate on the "global" layers' attention (a published
    # ``use_gqa_gate``): the heads' output times sigmoid(y W_gate)
    # elementwise, y the layer's normed input, before the output matrix
    attn_gate: bool = False
    # qk_norm over each head's head_dim (scale [head_dim]) instead of
    # over the whole projection
    qk_norm_per_head: bool = False
    # layer_mlp[i] is "dense" (width mlp_dim) or "sparse" (moe_experts
    # experts of width moe_mlp_dim or, when that is 0, mlp_dim);
    # () = every layer "sparse" when moe_experts is set
    layer_mlp: tuple = ()
    moe_mlp_dim: int = 0
    # the router (ops/moe.py): "softmax", or "sigmoid" scores with an
    # optional per-expert selection bias (it chooses, the score weighs)
    # and a factor on the gates; a shared expert of width moe_shared_dim
    # beside the routed ones; moe_held > 0 = expert parallelism's share:
    # the router is moe_experts wide, this device holds experts
    # 0..moe_held-1 and computes the pairs that land on them
    moe_router: str = "softmax"
    moe_select_bias: bool = False
    moe_routed_scale: float = 1.0
    moe_shared_dim: int = 0
    moe_held: int = 0
    # positions a window layer's decode ring holds (0 = attn_window).
    # The serving engine sets it: a slot's ring outlives the window by
    # what a pool commit reads back (serving/engine.py)
    window_ring: int = 0
    # -- a token mixer that is not attention: layer_attn[i] == "ssm" is a
    # Mamba-2 layer (``Mamba2Mixer``; a published ``layer_types`` entry
    # "mamba"): ssm_heads heads of ssm_head_dim with a state of
    # ssm_state a head element, B and C shared by the heads of each of
    # ssm_groups groups, a causal depthwise convolution over ssm_conv
    # positions, the scan in chunks of ssm_chunk
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_conv_bias: bool = True
    ssm_proj_bias: bool = False
    # what a decode model carries the recurrent state in between calls
    # (the arithmetic on it is float32 either way)
    ssm_state_dtype: Any = jnp.float32
    # -- layer_attn[i] == "kda" is a delta-rule linear-attention layer
    # (``KDAMixer``, ``ops/kda.py``; a published ``linear_attn_config``):
    # kda_heads heads with keys and values of kda_head_dim (also the
    # width of the decay's and the gate's low-rank path), a causal
    # depthwise convolution over kda_conv positions on q, k and v, the
    # chunked form in chunks of kda_chunk, the state a decode model
    # carries kept in kda_state_dtype
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_chunk: int = 64
    kda_state_dtype: Any = jnp.float32
    # beta = 2 sigmoid(b) in place of sigmoid(b) (a published
    # ``kda_allow_neg_eigval``): the transition I - beta k k^T then has
    # an eigenvalue in [-1, 1] and not in [0, 1]
    kda_neg_eigval: bool = False
    # -- layer_attn[i] == "latent" is a multi-head latent attention layer
    # (``LatentAttention``, ``ops/latent_attention.py``): num_heads
    # heads, keys and values through one latent of mla_rank
    # (``kv_lora_rank``) a token, mla_nope_dim key dims a head from it
    # and mla_rope_dim more that all heads share, values of mla_v_dim;
    # mla_rope rotates those shared dims (off: ``mla_use_nope``)
    mla_rank: int = 0
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    mla_rope: bool = False
    # a low-rank query (``q_lora_rank``): q = W_qb RMSNorm(W_qa y) in
    # place of one matrix; 0 = the one matrix
    mla_q_rank: int = 0
    # sandwich norms (``sandwich_norm``): a second RMSNorm on each
    # branch's OUTPUT before the residual add, x + N2(Mixer(N1(x))) and
    # x + N4(MLP(N3(x)))
    post_norms: bool = False
    # -- four scalars (Granite's): on the embedding, on both residual
    # branches, the attention scale in place of 1/sqrt(head_dim) (0 =
    # that default), and a divisor of the logits
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attn_scale: float = 0.0
    logits_scaling: float = 1.0
    # -- layer_attn[i] == "mamba1" is a Mamba-1 layer (``Mamba1Mixer``,
    # ``ops/mamba1.py``): m1_inner channels with a state of m1_state
    # each, a decay a (channel, state) pair, B, C and a dt of rank
    # m1_dt_rank read from the convolved x (a causal depthwise
    # convolution over m1_conv positions); the state a decode model
    # carries kept in ssm_state_dtype.  It also EMITS its scan output
    # (with the D term, before the gate), which a later layer_attn[i] ==
    # "gmu" (``GatedMemoryUnit``: no state, a gate of its own input on
    # that memory of the same token) reads
    m1_inner: int = 0
    m1_state: int = 16
    m1_conv: int = 4
    m1_dt_rank: int = 0
    # layer_attn[i] == "cross" is attention that projects a query only
    # and reads the keys and values the latest "global" layer below it
    # wrote (in a decode model: that layer's slab); it keeps no cache
    # -- differential attention (every attention layer of the plan):
    # heads pair by stripes, two softmaxes subtracted under a learned
    # lambda, an RMSNorm over the pair (``Block._diff_combine``)
    diff_attn: bool = False
    # "layer": every block and final norm is LayerNorm with scale and
    # bias in place of RMSNorm; attn_bias: biases on the attention
    # projections
    norm: str = "rms"
    attn_bias: bool = False

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.embed_dim // self.num_heads

    @property
    def pass_tokens(self) -> int:
        """Positions a slot a decode-shaped call of this model has: the
        two blocks of a block pass (``block_length`` with
        ``decode_scatter``: a commit half and an open half), else one
        token."""
        return (2 * self.block_length if self.decode and self.decode_scatter
                and self.block_length else 1)

    def is_pass(self, positions: int) -> bool:
        """Whether a call of ``positions`` a slot is a block pass: of
        both halves, or of the open half alone (no slot commits)."""
        return 1 < self.pass_tokens in (positions, 2 * positions)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    def attn_kind(self, layer: int) -> str:
        if self.layer_attn:
            return self.layer_attn[layer]
        return "window" if self.attn_window else "global"

    def mlp_kind(self, layer: int) -> str:
        if self.layer_mlp:
            return self.layer_mlp[layer]
        return "sparse" if self.moe_experts else "dense"

    @property
    def shares(self) -> bool:
        """Some layer reads what another computed (a memory, another
        layer's keys and values): the stack's loop carries it."""
        return bool({"mamba1", "gmu", "cross"} & set(self.layer_attn))

    @property
    def tail_start(self) -> int:
        """The first layer of the stack's TAIL: the last layers that
        keep nothing a position ("gmu", "cross").  A multi-token call of
        a decode model that samples at one row a lane runs them on that
        row alone (``TransformerLM``'s ``last_at``); ``num_layers``
        where the stack has no such tail."""
        i = self.num_layers
        while i and self.layer_attn and self.layer_attn[i - 1] in (
                "gmu", "cross"):
            i -= 1
        return i

    @property
    def uniform(self) -> bool:
        """Every layer alike: the stack can be one ``nn.scan``."""
        kinds = {(self.attn_kind(i), self.mlp_kind(i))
                 for i in range(self.num_layers)}
        return len(kinds) <= 1

    @property
    def ring_len(self) -> int:
        """Positions a window layer's decode cache holds."""
        return min(self.window_ring or self.attn_window, self.max_len)

    @property
    def expert_dim(self) -> int:
        return self.moe_mlp_dim or self.mlp_dim

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """What the convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def softmax_scale(self) -> float:
        return self.attn_scale or self.head_dim ** -0.5

    @property
    def sm_scale(self):
        """``dot_product_attention``'s ``sm_scale``: None is its own
        ``D ** -0.5`` of the rows it is given, which differential
        attention widens to a pair (``Block._diff_queries``)."""
        return self.softmax_scale if self.diff_attn else (
            self.attn_scale or None)

    @property
    def kda_inner(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def kda_proj_dim(self) -> int:
        """What a delta-rule layer's fused input projection gives: q, k
        and v, the decay's and the gate's low-rank inputs, and beta."""
        return 3 * self.kda_inner + 2 * self.kda_head_dim + self.kda_heads

    @property
    def mla_width(self) -> int:
        """Values a latent layer caches a token: the latent and the
        shared key dims."""
        return self.mla_rank + self.mla_rope_dim

    @property
    def mla_row(self) -> int:
        """The row a latent layer's decode cache keeps a token:
        ``mla_width`` in whole lane tiles."""
        return latent_attention.padded_width(self.mla_width)

    def mla_tiled(self, L: int) -> bool:
        """Whether a decode model's ``L``-token call runs a latent
        layer's expanded path as the kernel (``ops/latent_attention.
        expand_applies``): the one rule the model dispatches by and the
        engine counts and prices by."""
        return latent_attention.expand_applies(
            L, self.mesh, self.max_len, self.mla_row, self.dtype,
            self.mla_rank, self.mla_nope_dim, self.mla_v_dim)

    def __post_init__(self):
        for name, plan, kinds in (
                ("layer_attn", self.layer_attn,
                 ("window", "global", "ssm", "kda", "latent", "mamba1",
                  "gmu", "cross")),
                ("layer_mlp", self.layer_mlp, ("dense", "sparse"))):
            if plan and (len(plan) != self.num_layers
                         or set(plan) - set(kinds)):
                raise ValueError(
                    f"{name} must name one of {kinds} for each of "
                    f"{self.num_layers} layers, got {plan!r}")
        if "window" in self.layer_attn and not self.attn_window:
            raise ValueError("a window layer needs attn_window")
        if "ssm" in self.layer_attn and (
                self.ssm_heads < 1 or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                f"a state-space layer needs ssm_heads ({self.ssm_heads}) in "
                f"whole groups ({self.ssm_groups})")
        if "kda" in self.layer_attn and (
                self.kda_heads < 1 or self.kda_head_dim < 1
                or self.kda_conv < 1 or self.kda_chunk < 1):
            raise ValueError(
                f"a delta-rule layer needs kda_heads ({self.kda_heads}), "
                f"kda_head_dim, kda_conv and kda_chunk")
        if "latent" in self.layer_attn and (
                self.mla_rank < 1 or self.mla_nope_dim < 1
                or self.mla_rope_dim < 1 or self.mla_v_dim < 1
                or self.mla_rope_dim % 2):
            raise ValueError(
                f"a latent attention layer needs mla_rank ({self.mla_rank}), "
                f"mla_nope_dim, mla_v_dim and an even mla_rope_dim")
        if "mamba1" in self.layer_attn and (
                self.m1_inner < 1 or self.m1_state < 1 or self.m1_conv < 1
                or self.m1_dt_rank < 1):
            raise ValueError(
                f"a Mamba-1 layer needs m1_inner ({self.m1_inner}), m1_state, "
                f"m1_conv and m1_dt_rank ({self.m1_dt_rank})")
        for i, kind in enumerate(self.layer_attn):
            below = self.layer_attn[:i]
            if kind == "gmu" and "mamba1" not in below:
                raise ValueError(f"layer {i} is a gated memory unit with no "
                                 f"Mamba-1 layer below it to read")
            if kind == "cross" and "global" not in below:
                raise ValueError(f"layer {i} is a cross layer with no global "
                                 f"layer below it to read")
        if self.diff_attn and (self.num_heads % 4 or self.kv_heads % 2
                               or self.num_heads != 2 * self.kv_heads):
            raise ValueError(
                f"differential attention pairs heads by stripes: num_heads "
                f"({self.num_heads}) twice kv_heads ({self.kv_heads}), both "
                f"in whole pairs")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"norm {self.norm!r} is neither rms nor layer")
        if "sparse" in self.layer_mlp and not self.moe_experts:
            raise ValueError("a sparse layer needs moe_experts")
        if self.moe_held and not 0 < self.moe_held <= self.moe_experts:
            raise ValueError(
                f"moe_held {self.moe_held} of {self.moe_experts} experts")
        if self.block_length < 0:
            raise ValueError(f"block_length {self.block_length} < 0")
        if self.block_length and (
                self.attn_window or self.diff_attn
                or set(self.layer_attn) - {"global"}):
            raise ValueError(
                "block_length > 0 (block-causal attention) is written for a "
                "stack of plain global attention layers: no window, no "
                "differential attention, no recurrent, latent or borrowing "
                f"layer (layer_attn {self.layer_attn!r}, attn_window "
                f"{self.attn_window})")
        if self.block_length:
            if self.block_remasking not in ("static", "dynamic"):
                raise ValueError(f"block_remasking {self.block_remasking!r} "
                                 f"is neither static nor dynamic")
            if not 0 <= self.block_mask_id < self.vocab_size:
                raise ValueError(f"block_mask_id {self.block_mask_id} is no "
                                 f"row of the vocabulary ({self.vocab_size})")
            if not 0 <= self.block_steps <= self.block_length:
                raise ValueError(f"block_steps {self.block_steps} outside "
                                 f"1..{self.block_length}")


def _layer_matmul_params(cfg: TransformerConfig, experts: int,
                         layer: int = 0) -> int:
    """Layer ``layer``'s matmul parameters with ``experts`` routed
    experts counted (those held, or the ``moe_top_k`` a token is routed
    to); a shared expert counts whole either way."""
    D = cfg.embed_dim
    H, Hk, Dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    kind = cfg.attn_kind(layer)
    if kind == "ssm":
        attn = (D * (cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads)
                + cfg.ssm_inner * D)
    elif kind == "kda":
        attn = (D * cfg.kda_proj_dim + 2 * cfg.kda_head_dim * cfg.kda_inner
                + cfg.kda_inner * D)
    elif kind == "mamba1":
        attn = (D * 2 * cfg.m1_inner
                + cfg.m1_inner * (cfg.m1_dt_rank + 2 * cfg.m1_state)
                + cfg.m1_dt_rank * cfg.m1_inner + cfg.m1_inner * D)
    elif kind == "gmu":
        attn = 2 * D * cfg.m1_inner
    elif kind == "cross":
        attn = 2 * D * H * Dh
    elif kind == "latent":
        attn = (D * cfg.mla_q_rank
                + (cfg.mla_q_rank or D) * H * (cfg.mla_nope_dim
                                               + cfg.mla_rope_dim)
                + D * cfg.mla_width
                + cfg.mla_rank * H * (cfg.mla_nope_dim + cfg.mla_v_dim)
                + H * cfg.mla_v_dim * D)
    else:
        attn = D * (H + 2 * Hk) * Dh + H * Dh * D
        if cfg.attn_gate and kind == "global":
            attn += D * H * Dh
    if cfg.mlp_kind(layer) == "dense":
        return attn + 3 * D * cfg.mlp_dim
    return (attn + D * cfg.moe_experts + 3 * D * cfg.moe_shared_dim
            + experts * (3 if cfg.moe_gated else 2) * D * cfg.expert_dim)


def param_count(cfg: TransformerConfig) -> int:
    """Parameter count of the config (embedding table included; with
    ``moe_held``, of the experts this device holds)."""
    D, V = cfg.embed_dim, cfg.vocab_size
    # a layer's block norms (a LayerNorm has a bias too)
    pre = (4 if cfg.post_norms else 2) * D * (2 if cfg.norm == "layer" else 1)
    norms = pre
    if cfg.qk_norm:
        norms += (2 * cfg.head_dim if cfg.qk_norm_per_head
                  else (cfg.num_heads + cfg.kv_heads) * cfg.head_dim)
    head = 0 if cfg.tie_embeddings else D * V
    held = cfg.moe_held or cfg.moe_experts
    bias = cfg.moe_experts if cfg.moe_select_bias else 0
    # a state-space layer: the convolution, dt_bias, A_log and D, the
    # gated norm's scale in place of any q / k norm
    ssm_own = (cfg.ssm_conv_dim * (cfg.ssm_conv + cfg.ssm_conv_bias)
               + 3 * cfg.ssm_heads + cfg.ssm_inner
               + (cfg.ssm_inner + cfg.ssm_conv_dim + cfg.ssm_heads
                  + D if cfg.ssm_proj_bias else 0))
    # a delta-rule layer: the convolution, dt_bias, A_log and the output
    # norm's scale; a latent layer: the latent's norm and a low-rank
    # query's
    own = {"ssm": pre + ssm_own,
           "kda": (pre + 3 * cfg.kda_inner * cfg.kda_conv + cfg.kda_inner
                   + cfg.kda_heads + cfg.kda_head_dim),
           "latent": pre + cfg.mla_rank + cfg.mla_q_rank,
           # a Mamba-1 layer: the convolution and its bias, dt's bias,
           # A_log and D; a gated memory unit: nothing of its own
           "mamba1": pre + cfg.m1_inner * (cfg.m1_conv + 3 + cfg.m1_state),
           "gmu": pre}
    # differential attention: four lambda vectors and the pair norm's
    # scale a layer; biases on q (k, v) and the output
    H, Hk, Dh = cfg.num_heads, cfg.kv_heads, cfg.head_dim
    extra = 6 * Dh if cfg.diff_attn else 0
    own["cross"] = pre + extra + (H * Dh + D if cfg.attn_bias else 0)
    norms += extra + ((H + 2 * Hk) * Dh + D if cfg.attn_bias else 0)
    final = D * (2 if cfg.norm == "layer" else 1)
    return V * D + head + final + sum(
        _layer_matmul_params(cfg, held, i)
        + own.get(cfg.attn_kind(i), norms)
        + (bias if cfg.mlp_kind(i) == "sparse" else 0)
        for i in range(cfg.num_layers))


def active_matmul_params(cfg: TransformerConfig) -> int:
    """Parameters that take part in a matmul for ONE token: attention,
    the head, and the MLP - for an expert configuration the router and
    the ``moe_top_k`` experts a token is routed to, not all of them.
    The embedding table is a lookup."""
    return (sum(_layer_matmul_params(cfg, cfg.moe_top_k, i)
                for i in range(cfg.num_layers))
            + cfg.embed_dim * cfg.vocab_size)


# Calibrated on v5e: the flagship (12L x 768, seq 1024)
# trains without remat at bs 8 (~9 GB estimated, fits 16 GB) and OOMs
# by ~0.9 GB at bs 16 (~16.5 GB estimated) — both predicted correctly
# by ~48 bf16-equivalent activation values per token x layer x embed.
_ACT_VALS_PER_TOK_LAYER_EMBED = 48


def auto_layout(cfg: TransformerConfig, per_device_batch: int,
                seq: int | None = None,
                hbm_bytes: float | None = None) -> TransformerConfig:
    """Resolve the two perf-critical layout knobs automatically so the
    SHIPPED defaults hit the advertised throughput (round-4 verdict
    weak #4: the tuned numbers needed non-default env knobs):

    - ``scan_layers``: unroll when ``num_layers <= 16`` — the scan's
      residual-stacking copies cost ~12% step time (profiled ~11 ms at
      the flagship config) and the unrolled compile stays ~1 min at
      that depth; deeper stacks keep the scan for compile time;
    - ``remat``: off whenever the estimated train footprint (f32
      params + adam moments + activations) fits 90% of the device's
      HBM at this batch — remat there costs ~8% for nothing.

    The estimate is conservative and calibrated on measured v5e runs
    (see ``_ACT_VALS_PER_TOK_LAYER_EMBED``).  ``hbm_bytes`` defaults to
    the device's reported limit (16 GB-class when unreported).
    """
    from dataclasses import replace

    if hbm_bytes is None:
        dev = jax.local_devices()[0]    # peers' devices have no stats
        stats = dev.memory_stats() or {}
        if dev.platform == "tpu" and not stats.get("bytes_limit"):
            raise RuntimeError(
                f"{dev.device_kind} reports no bytes_limit "
                f"(memory_stats()={stats!r}); pass hbm_bytes")
        # CPU/test backends report no limit: size as a 16 GB-class chip
        hbm_bytes = float(stats.get("bytes_limit") or 16e9)
    remat = (train_bytes_estimate(cfg, per_device_batch, seq or cfg.max_len)
             > 0.9 * hbm_bytes)
    return replace(cfg, remat=remat, scan_layers=cfg.num_layers > 16)


def attention_kept_bytes(cfg: TransformerConfig, per_device_batch: int,
                         seq: int) -> int:
    """What the attention layers' forward kernels hand their backward,
    on one device for a whole step: ``out`` in the compute dtype
    ``[B, S, H, Dh]`` and the logsumexp in f32 ``[B, H, S]`` a layer.
    ``_remat`` keeps both by name beside the matmuls' outputs, so they
    are held for every layer at once whether remat is on or off: 136 MB
    a layer at 4 x 4096 tokens of 32 heads of 128."""
    layers = sum(cfg.attn_kind(i) in ("window", "global")
                 for i in range(cfg.num_layers))
    rows = per_device_batch * seq * cfg.num_heads
    return layers * rows * (cfg.head_dim * jnp.dtype(cfg.dtype).itemsize + 4)


def attention_backward_bytes(cfg: TransformerConfig, per_device_batch: int,
                             seq: int) -> int:
    """The buffer ONE attention layer's backward kernel writes beside
    its gradients, the largest over the stack's layers (one layer's
    backward runs at a time): the fused splash backward's partial dQs,
    0.54 GB at 4 x 4096 tokens of 32 heads of 128."""
    windows = {cfg.attn_window if kind == "window" else 0
               for kind in map(cfg.attn_kind, range(cfg.num_layers))
               if kind in ("window", "global")}
    return max((splash_partials_bytes(
        per_device_batch, seq, cfg.num_heads, cfg.head_dim, w,
        jnp.dtype(cfg.dtype).itemsize) for w in windows), default=0)


def train_bytes_estimate(cfg: TransformerConfig, per_device_batch: int,
                         seq: int) -> int:
    """The train footprint :func:`auto_layout` decides from, without
    remat: state, activations, the head's logits, what attention keeps
    for its backward and what that backward writes beside its
    gradients."""
    state_bytes = 16 * param_count(cfg)     # f32 params + adam m/v + grads
    act_bytes = (2 * per_device_batch * seq * cfg.num_layers * cfg.embed_dim
                 * _ACT_VALS_PER_TOK_LAYER_EMBED)
    # the head's [B, S, V] f32 logits (+ their softmax/grad twin) scale
    # with VOCAB, not layers x embed — omitting them under-predicts
    # vocab-heavy configs in the dangerous direction (remat off, OOM).
    # The fused-CE loss path never materialises them, but auto_layout
    # cannot know which loss the caller uses; estimate conservatively.
    logits_bytes = 2 * 4 * per_device_batch * seq * cfg.vocab_size
    return (state_bytes + act_bytes + logits_bytes
            + attention_kept_bytes(cfg, per_device_batch, seq)
            + attention_backward_bytes(cfg, per_device_batch, seq))


def rope(x, positions, theta: float):
    """Rotary position embedding over the last dim of [B, L, H, D]."""
    D = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, L, D/2]
    cos, sin = jnp.cos(angles)[:, :, None], jnp.sin(angles)[:, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def _pin(cfg: "TransformerConfig", x, *axes):
    """Say where a training activation lives, by logical axis names.

    Weights carry ``embed -> fsdp``; an activation never does.  Left to
    choose, GSPMD keeps a weight's ``fsdp`` shard in place, gathers the
    ACTIVATION to the whole batch and all-reduces every matmul's product
    between chips (PERF.md section 6, PR 29).  With the activation fixed
    on the batch axes the one way left to compute ``y @ W`` is to gather
    ``W``: ZeRO-3.  The same names give ``tp`` its Megatron layout
    (``heads`` / ``mlp``) and ``sp`` its sequence shards.  Nothing is
    emitted without a mesh (``parallel/sharding.logical_constraint``)
    or in a decode model, whose layouts the engine owns."""
    if cfg.decode:
        return x
    return logical_constraint(x, axes, cfg.mesh)


def _fences_norms(cfg: "TransformerConfig") -> bool:
    """Whether a block's norms stand between two fences: in a model that
    is not a decode model and whose layer weights lie whole on every
    device (no mesh, a mesh of one device, a mesh that splits the batch
    alone).  There each matmul beside a norm is one whole op and XLA
    puts the norm's reductions into it (``mlp_out`` / ``attn_out``
    forward carry the next norm's sum of squares, the backward of
    ``mlp_gate`` / ``attn_qkv`` the norm's whole backward): on one v5e
    chip those ran at 45-64% of the peak where their plain twins run at
    80-88%, and the fence gave the step 2.9% (PERF.md section 6, PR 47;
    a ``dp=4`` step compiled for ``v5e:2x2`` holds the same eight
    fusions and loses them to the fence).  Where the weights are split
    (``fsdp``, ``tp``, ``ep``) the weight gathers and ``_pin``'s
    constraints already cut every layer matmul into pieces that carry no
    reduce, and a fence only adds the norms' own passes: on four chips
    under ``fsdp=4`` it cost the step 0.85% (same place).  A decode
    model's matmuls are a few rows against whole weights."""
    if cfg.decode:
        return False
    return cfg.mesh is None or logical_sharding(
        ("embed", "mlp", "expert"), cfg.mesh).is_fully_replicated


class RMSNorm(nn.Module):
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(self.dtype) * scale


class LayerNorm(nn.Module):
    """Mean and variance over the last axis in float32, a scale and a
    bias; the output as ``RMSNorm`` gives it (the compute dtype, times a
    float32 scale)."""

    dtype: Any = jnp.bfloat16
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                          jnp.float32)
        x = x.astype(jnp.float32)
        x = x - jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x), -1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(self.dtype
                                                          ) * scale + bias


def _norm(cfg: "TransformerConfig", name: str):
    """A block's (or the final) norm: ``cfg.norm`` says which.  Where
    ``_fences_norms`` holds, its input and its output each pass
    ``optimization_barrier`` (the identity, opaque to fusion): the norm
    runs as passes of its own, forward and backward."""
    kind = LayerNorm if cfg.norm == "layer" else RMSNorm
    norm = kind(cfg.dtype, cfg.norm_eps, name=name)
    if not _fences_norms(cfg):
        return norm
    fence = jax.lax.optimization_barrier
    return lambda x: fence(norm(fence(x)))


def _residual(cfg: "TransformerConfig", x, branch):
    """``x + residual_multiplier * branch``; the product and the sum in
    float32 where the multiplier is not 1 (0.22 is not a bfloat16)."""
    m = cfg.residual_multiplier
    if m == 1.0:
        return x + branch
    return (x.astype(jnp.float32) + m * branch.astype(jnp.float32)).astype(
        x.dtype)


def _gate_norm(o, z, norm):
    """Gate first, then norm (one group over the whole inner width)."""
    return norm(o * nn.silu(z))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a log-uniform step in [1e-3, 1e-1]."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype)
                 * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _short_conv(conv0, x, conv_w):
    """A causal depthwise convolution over ``K = conv_w.shape[0]``
    positions, FROM ``conv0 [B, K - 1, C]`` (the inputs before the
    call's first position; a decode model's ``conv_state``): ``(the
    float32 sums [B, L, C], the inputs it ran over [B, K - 1 + L, C],
    window)`` with ``window(at [B])`` the ``K - 1`` inputs before
    position ``at`` of the call: what a call that resumes there has to
    be given as ``conv0``."""
    K, L = conv_w.shape[0], x.shape[1]
    xin = jnp.concatenate([conv0, x.astype(conv0.dtype)], axis=1)
    acc = sum(xin[:, i:i + L].astype(jnp.float32) * conv_w[i]
              for i in range(K))

    def window(at):
        idx = at[:, None] + jnp.arange(K - 1)[None, :]
        return jnp.take_along_axis(xin, idx[:, :, None], axis=1)

    return acc, xin, window


class Mamba2Mixer(nn.Module):
    """A Mamba-2 token mixer (``ops/ssm.py`` has the recurrence): with
    ``y`` the layer's normed input,

    ``[z | xBC | dt] = y W_in``; ``xBC`` through a causal depthwise
    convolution over ``ssm_conv`` positions and a SiLU; ``[x | B | C] =
    xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the
    recurrence; ``o = y_ssm + D * x``; ``RMSNorm(o * silu(z))`` over the
    whole inner width (gate first, then norm, one group); ``W_out``.

    In a decode model its ``cache`` is not keys and values but a
    recurrence: ``conv_state [B, ssm_conv - 1, conv_dim]`` (the inputs
    the next position's convolution looks back on, in the compute
    dtype; time-major, so that the jit boundary's layout does not pad
    three positions to a lane tile), ``ssm_state [B, H, P, N]`` float32,
    and ``cache_index`` as every layer keeps it.  A one-token call
    updates them in place (``ops/ssm.ssm_step`` on the chip; slots that
    ``token_mask`` marks free keep theirs).  A multi-token call (prefill,
    a chunk, a reuse suffix) runs the chunked scan FROM the cached
    state and leaves the state after each lane's last REAL token
    (``token_mask`` [B, L], real tokens leading): a padded position must
    not move a recurrence.  ``snap_at`` [B] asks such a call for the
    state after ``snap_at`` of its tokens too, sown into the ``snap``
    collection under the cache's names: where the serving engine snapshots
    a prompt for its prefix pool."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, y, token_mask=None, snap_at=None):
        cfg = self.cfg
        f32 = jnp.float32
        H, P, N, G = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                      cfg.ssm_groups)
        K, Di, Cd = cfg.ssm_conv, cfg.ssm_inner, cfg.ssm_conv_dim
        B, L = y.shape[:2]
        with jax.named_scope("ssm/proj_in"):
            zxbcdt = nn.Dense(Di + Cd + H, use_bias=cfg.ssm_proj_bias,
                              dtype=cfg.dtype, param_dtype=f32,
                              name="in_proj")(y)
        z, xBC, dt = jnp.split(zxbcdt, [Di, Di + Cd], axis=-1)
        conv_w = self.param("conv_w", nn.initializers.normal(K ** -0.5),
                            (K, Cd), f32)
        conv_b = (self.param("conv_b", nn.initializers.zeros, (Cd,), f32)
                  if cfg.ssm_conv_bias else None)
        dt_bias = self.param("dt_bias", _dt_bias_init, (H,), f32)
        A = -jnp.exp(self.param("A_log", _a_log_init, (H,), f32))
        D_skip = self.param("D", nn.initializers.ones, (H,), f32)

        cached = cfg.decode and self.has_variable("cache", "ssm_state")
        if cfg.decode:
            conv_v = self.variable("cache", "conv_state", jnp.zeros,
                                   (B, K - 1, Cd), cfg.dtype)
            ssm_v = self.variable("cache", "ssm_state", jnp.zeros,
                                  (B, H, P, N), cfg.ssm_state_dtype)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((B,), jnp.int32))
        conv0 = (conv_v.value if cached
                 else jnp.zeros((B, K - 1, Cd), cfg.dtype))
        state0 = (ssm_v.value.astype(f32) if cached
                  else jnp.zeros((B, H, P, N), f32))
        kept = cfg.ssm_state_dtype
        n_real = (token_mask.sum(-1).astype(jnp.int32)
                  if cached and token_mask is not None else None)

        with jax.named_scope("ssm/conv"):
            acc, xin, window = _short_conv(conv0, xBC, conv_w)
            if conv_b is not None:
                acc = acc + conv_b
            xBC = nn.silu(acc)
        x, Bm, Cm = jnp.split(xBC, [Di, Di + G * N], axis=-1)
        x = x.reshape(B, L, H, P)
        Bm, Cm = Bm.reshape(B, L, G, N), Cm.reshape(B, L, G, N)
        dt = jax.nn.softplus(dt.astype(f32) + dt_bias)

        if cached and L == 1:
            live = (jnp.ones((B,), bool) if token_mask is None
                    else token_mask[:, 0])
            kernel = kept == f32 and ssm.applies(L, cfg.mesh)
            step = ssm.ssm_step if kernel else ssm.ssm_step_reference
            with jax.named_scope("ssm/step"):
                ys, new = step(state0, x[:, 0], dt[:, 0], A, Bm[:, 0],
                               Cm[:, 0], live)
            # slot states this update read and wrote, for the engine's
            # ``ssm_state_steps_run``: the kernel's own fetch plan
            # counted; the einsum path touches every slot
            self.sow("intermediates", "ssm_slots_run",
                     ssm.slots_fetched(live, H, P, N, G) if kernel
                     else jnp.asarray(B, f32))
            ys, ssm_v.value = ys[:, None], new.astype(kept)
            conv_v.value = jnp.where(live[:, None, None], xin[:, 1:], conv0)
        else:
            ys, final, snap = ssm.ssd_scan(
                x, dt, A, Bm, Cm, state0, chunk=cfg.ssm_chunk,
                lengths=n_real, snap_at=snap_at if cached else None)
            if cached:
                ssm_v.value = final.astype(kept)
                conv_v.value = window(
                    jnp.full((B,), L, jnp.int32) if n_real is None
                    else n_real)
                if snap is not None:
                    at = jnp.clip(snap_at.astype(jnp.int32), 0,
                                  L if n_real is None else n_real)
                    # named as the cache names them
                    self.sow("snap", "ssm_state", snap.astype(kept))
                    self.sow("snap", "conv_state", window(at))
        if cached:
            ci.value = ci.value + L
        with jax.named_scope("ssm/gate_norm"):
            o = ys + D_skip[:, None] * x.astype(f32)
            u = _gate_norm(o.reshape(B, L, Di), z.astype(f32),
                           RMSNorm(cfg.dtype, cfg.norm_eps, name="norm")
                           ).astype(cfg.dtype)
        with jax.named_scope("ssm/proj_out"):
            return nn.Dense(cfg.embed_dim, use_bias=cfg.ssm_proj_bias,
                            dtype=cfg.dtype, param_dtype=f32,
                            name="out_proj")(u)


class Mamba1Mixer(nn.Module):
    """A Mamba-1 token mixer (``ops/mamba1.py`` has the recurrence): with
    ``u`` the layer's normed input,

    ``[x | z] = u W_in``; ``x`` through a causal depthwise convolution
    over ``m1_conv`` positions with a bias and a SiLU; ``[dt_r | B | C]
    = x W_x``; ``dt = softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``
    ``[inner, N]``; the recurrence; ``y = y_ssm + D * x``;
    ``W_out (y * silu(z))``.  Returns ``(out, y)``: ``y`` is the MEMORY
    a gated memory unit further up reads.

    In a decode model its ``cache`` is ``Mamba2Mixer``'s contract to the
    letter: ``conv_state [B, m1_conv - 1, inner]`` (the compute dtype),
    ``ssm_state [B, N, inner]`` (``ssm_state_dtype``; the wide axis on
    the lanes) and ``cache_index``; one-token calls update in place
    (``ops/mamba1.mamba1_step`` on the chip, free slots keep theirs),
    multi-token calls run the scan FROM the cached state to each lane's
    last REAL token, ``snap_at`` sows the state at one more position
    into the ``snap`` collection under the cache's names."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u, token_mask=None, snap_at=None):
        cfg = self.cfg
        f32 = jnp.float32
        Di, N, K, R = cfg.m1_inner, cfg.m1_state, cfg.m1_conv, cfg.m1_dt_rank
        B, L = u.shape[:2]

        def dense(width, name, **kw):
            return nn.Dense(width, dtype=cfg.dtype, param_dtype=f32,
                            name=name, **{"use_bias": False, **kw})

        with jax.named_scope("mamba1/proj_in"):
            x, z = jnp.split(dense(2 * Di, "in_proj")(u), 2, axis=-1)
        conv_w = self.param("conv_w", nn.initializers.normal(K ** -0.5),
                            (K, Di), f32)
        conv_b = self.param("conv_b", nn.initializers.zeros, (Di,), f32)
        A = -jnp.exp(self.param("A_log", _a_log_init, (Di, N), f32)).T
        D_skip = self.param("D", nn.initializers.ones, (Di,), f32)

        cached = cfg.decode and self.has_variable("cache", "ssm_state")
        kept = cfg.ssm_state_dtype
        if cfg.decode:
            conv_v = self.variable("cache", "conv_state", jnp.zeros,
                                   (B, K - 1, Di), cfg.dtype)
            ssm_v = self.variable("cache", "ssm_state", jnp.zeros,
                                  (B, N, Di), kept)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((B,), jnp.int32))
        conv0 = (conv_v.value if cached
                 else jnp.zeros((B, K - 1, Di), cfg.dtype))
        state0 = (ssm_v.value.astype(f32) if cached
                  else jnp.zeros((B, N, Di), f32))
        n_real = (token_mask.sum(-1).astype(jnp.int32)
                  if cached and token_mask is not None else None)

        with jax.named_scope("mamba1/conv"):
            acc, xin, window = _short_conv(conv0, x, conv_w)
            x = nn.silu(acc + conv_b).astype(cfg.dtype)
        with jax.named_scope("mamba1/proj_x"):
            dt, Bm, Cm = jnp.split(dense(R + 2 * N, "x_proj")(x),
                                   [R, R + N], axis=-1)
            dt = jax.nn.softplus(dense(
                Di, "dt_proj", use_bias=True, bias_init=_dt_bias_init)(dt)
                .astype(f32))

        if cached and L == 1:
            live = (jnp.ones((B,), bool) if token_mask is None
                    else token_mask[:, 0])
            kernel = kept == f32 and mamba1.applies(L, cfg.mesh)
            step = (mamba1.mamba1_step if kernel
                    else mamba1.mamba1_step_reference)
            with jax.named_scope("mamba1/step"):
                ys, new = step(state0, x[:, 0], dt[:, 0], A, Bm[:, 0],
                               Cm[:, 0], live)
            # slot states this update read and wrote: the engine's
            # ``ssm_state_steps_run`` counts any state layer's
            self.sow("intermediates", "ssm_slots_run",
                     mamba1.slots_fetched(live) if kernel
                     else jnp.asarray(B, f32))
            ys, ssm_v.value = ys[:, None], new.astype(kept)
            conv_v.value = jnp.where(live[:, None, None], xin[:, 1:], conv0)
        else:
            ys, final, snap = mamba1.selective_scan(
                x, dt, A, Bm, Cm, state0, lengths=n_real,
                snap_at=snap_at if cached else None)
            if cached:
                ssm_v.value = final.astype(kept)
                conv_v.value = window(
                    jnp.full((B,), L, jnp.int32) if n_real is None
                    else n_real)
                if snap is not None:
                    at = jnp.clip(snap_at.astype(jnp.int32), 0,
                                  L if n_real is None else n_real)
                    # named as the cache names them
                    self.sow("snap", "ssm_state", snap.astype(kept))
                    self.sow("snap", "conv_state", window(at))
        if cached:
            ci.value = ci.value + L
        with jax.named_scope("mamba1/gate"):
            y = ys + D_skip * x.astype(f32)
            gated = (y * nn.silu(z.astype(f32))).astype(cfg.dtype)
        with jax.named_scope("mamba1/proj_out"):
            return dense(cfg.embed_dim, "out_proj")(gated), y.astype(cfg.dtype)


class GatedMemoryUnit(nn.Module):
    """A mixer with no state: ``W_out (m * silu(W_in u))`` with ``m`` the
    memory a Mamba-1 layer below emitted FOR THE SAME TOKEN."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, u, memory):
        cfg = self.cfg
        with jax.named_scope("gmu"):
            gate = nn.Dense(cfg.m1_inner, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name="in_proj")(u)
            gated = (memory.astype(jnp.float32)
                     * nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
            return nn.Dense(cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name="out_proj")(gated)


class KDAMixer(nn.Module):
    """A delta-rule linear-attention token mixer (Kimi delta attention;
    ``ops/kda.py`` has the recurrence): with ``y`` the layer's normed
    input and ``R = kda_head_dim``,

    ``[q | k | v | f | z | b] = y W_in`` (one fused projection: ``f`` and
    ``z`` the ``R``-wide inputs of the decay's and the gate's low-rank
    paths, ``b`` a head's beta logit); ``q | k | v`` through a causal
    depthwise convolution over ``kda_conv`` positions (no bias) and a
    SiLU; ``q``, ``k`` L2-normalised a head and ``q`` scaled by
    ``R ** -0.5``; ``g = -exp(A_log) * softplus(f W_f + dt_bias)`` a key
    channel, ``beta = sigmoid(b)`` in [0, 1], or ``2 sigmoid(b)`` in [0,
    2] under ``kda_neg_eigval`` (the benchmark's
    ``solar-open2-250b-serve-ep8`` doubles it; ``kimi-linear-48b-a3b-
    serve-ep4`` does not); the recurrence; ``RMSNorm_head(o) *
    sigmoid(z W_g)``; ``W_out``.

    In a decode model its ``cache`` is ``conv_state [B, kda_conv - 1, 3
    H R]`` (time-major, the compute dtype), ``kda_state [B, H, R, R]``
    (``kda_state_dtype``) and ``cache_index``: ``Mamba2Mixer``'s
    contract to the letter (one-token calls update in place,
    ``ops/kda.kda_step`` on the chip, free slots keep theirs; multi-token
    calls run the chunked form FROM the cached state to each lane's last
    REAL token, the lanes one after another where one lane's blocks are
    large, ``ops/kda.lanes_mapped``; ``snap_at`` sows the state at one
    more position into the ``snap`` collection under the cache's
    names)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, y, token_mask=None, snap_at=None):
        cfg = self.cfg
        f32 = jnp.float32
        H, R, K = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_conv
        Di = cfg.kda_inner
        B, L = y.shape[:2]

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=f32, name=name)

        with jax.named_scope("kda/proj_in"):
            qkv, f, z, b = jnp.split(
                dense(cfg.kda_proj_dim, "in_proj")(y),
                [3 * Di, 3 * Di + R, 3 * Di + 2 * R], axis=-1)
            f = dense(Di, "f_proj")(f)
        conv_w = self.param("conv_w", nn.initializers.normal(K ** -0.5),
                            (K, 3 * Di), f32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (Di,), f32)
        A = -jnp.exp(self.param("A_log", _a_log_init, (H,), f32))

        cached = cfg.decode and self.has_variable("cache", "kda_state")
        if cfg.decode:
            conv_v = self.variable("cache", "conv_state", jnp.zeros,
                                   (B, K - 1, 3 * Di), cfg.dtype)
            state_v = self.variable("cache", "kda_state", jnp.zeros,
                                    (B, H, R, R), cfg.kda_state_dtype)
            ci = self.variable("cache", "cache_index",
                               lambda: jnp.zeros((B,), jnp.int32))
        conv0 = (conv_v.value if cached
                 else jnp.zeros((B, K - 1, 3 * Di), cfg.dtype))
        state0 = (state_v.value.astype(f32) if cached
                  else jnp.zeros((B, H, R, R), f32))
        kept = cfg.kda_state_dtype
        n_real = (token_mask.sum(-1).astype(jnp.int32)
                  if cached and token_mask is not None else None)

        with jax.named_scope("kda/conv"):
            acc, xin, window = _short_conv(conv0, qkv, conv_w)
            qkv = nn.silu(acc)
            q, k, v = (a.reshape(B, L, H, R)
                       for a in jnp.split(qkv, 3, axis=-1))
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6
                                  ) * R ** -0.5
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        g = (jax.nn.softplus(f.astype(f32) + dt_bias).reshape(B, L, H, R)
             * A[:, None])
        beta = jax.nn.sigmoid(b.astype(f32))                     # [B, L, H]
        if cfg.kda_neg_eigval:
            beta = 2.0 * beta

        if cached and L == 1:
            live = (jnp.ones((B,), bool) if token_mask is None
                    else token_mask[:, 0])
            kernel = kept == f32 and kda.applies(L, cfg.mesh)
            step = kda.kda_step if kernel else kda.kda_step_reference
            with jax.named_scope("kda/step"):
                o, new = step(state0, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                              beta[:, 0], live)
            # slot states this update read and wrote: the engine's
            # ``ssm_state_steps_run`` counts any state layer's
            self.sow("intermediates", "ssm_slots_run",
                     kda.slots_fetched(live, H, R, R) if kernel
                     else jnp.asarray(B, f32))
            o, state_v.value = o[:, None], new.astype(kept)
            conv_v.value = jnp.where(live[:, None, None], xin[:, 1:], conv0)
        else:
            o, final, snap = kda.kda_chunked(
                q, k, v, g, beta, state0, chunk=cfg.kda_chunk,
                lengths=n_real, snap_at=snap_at if cached else None)
            if cached:
                state_v.value = final.astype(kept)
                conv_v.value = window(
                    jnp.full((B,), L, jnp.int32) if n_real is None
                    else n_real)
                if snap is not None:
                    at = jnp.clip(snap_at.astype(jnp.int32), 0,
                                  L if n_real is None else n_real)
                    # named as the cache names them
                    self.sow("snap", "kda_state", snap.astype(kept))
                    self.sow("snap", "conv_state", window(at))
        if cached:
            ci.value = ci.value + L
        with jax.named_scope("kda/gate_norm"):
            gate = jax.nn.sigmoid(dense(Di, "g_proj")(z).astype(f32))
            u = (RMSNorm(cfg.dtype, cfg.norm_eps, name="o_norm")(o).astype(f32)
                 .reshape(B, L, Di) * gate).astype(cfg.dtype)
        with jax.named_scope("kda/proj_out"):
            return dense(cfg.embed_dim, "o_proj")(u)


class LatentAttention(nn.Module):
    """A multi-head latent attention mixer (``ops/latent_attention.py``
    has the two paths and the kernels): with ``y`` the normed input,

    ``q = y W_q`` (with ``mla_q_rank`` ``W_qb RMSNorm(y W_qa)``) ->
    ``[H, nope + rope]``; ``[c' | k_pe] = y W_kva``;
    ``c = RMSNorm(c')``; ``[k_nope | v]_h = W_kvb[:, h]^T c``; scores
    ``(q_nope . k_nope + q_pe . k_pe) / sqrt(nope + rope)``, causal,
    float32 softmax; ``W_o``.  ``q_pe`` and ``k_pe`` are rotated only
    with ``mla_rope``.

    In a decode model the ``cache`` is ``cached_latent [B, max_len,
    mla_row]``: ONE row ``c | k_pe`` a token (zeros up to whole lane
    tiles), after the norm, no head axis, keys and values the same
    bytes; and ``cache_index``.  A one-token call appends a row and
    attends on the absorbed path (on the chip ``latent_append`` and
    ``latent_attend``, which read live positions of live slots once;
    slots that ``token_mask`` marks free are neither written nor read);
    a multi-token call writes its rows at the (batch-uniform) index and
    attends the slot's rows up to its own last position on the expanded
    path, in tiles (the rows past them are not read; on the chip the
    kernel ``latent_expand_tiled``, elsewhere its XLA loop)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, y, positions, token_mask=None):
        cfg = self.cfg
        H, rank = cfg.num_heads, cfg.mla_rank
        nope, rope_d, vd = cfg.mla_nope_dim, cfg.mla_rope_dim, cfg.mla_v_dim
        B, L = y.shape[:2]
        scale = cfg.attn_scale or (nope + rope_d) ** -0.5

        def dense(width, name):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=jnp.float32, name=name)

        if cfg.mla_q_rank:
            with jax.named_scope("attn/latent_q_lora"):
                q = dense(H * (nope + rope_d), "q_b")(
                    RMSNorm(cfg.dtype, cfg.norm_eps, name="q_norm")(
                        dense(cfg.mla_q_rank, "q_a")(y)).astype(cfg.dtype))
        else:
            q = dense(H * (nope + rope_d), "q_proj")(y)
        q = q.reshape(B, L, H, nope + rope_d)
        ckv = dense(cfg.mla_width, "kv_a")(y)
        c = RMSNorm(cfg.dtype, cfg.norm_eps, name="kv_norm")(
            ckv[..., :rank]).astype(cfg.dtype)
        k_pe = ckv[..., rank:]
        if cfg.mla_rope:
            q = jnp.concatenate(
                [q[..., :nope], rope(q[..., nope:], positions,
                                     cfg.rope_theta)], axis=-1)
            k_pe = rope(k_pe[:, :, None], positions, cfg.rope_theta)[:, :, 0]
        latent = jnp.concatenate([c, k_pe], axis=-1)             # [B, L, W]
        w_kvb = self.param("kv_b", nn.initializers.lecun_normal(),
                           (rank, H * (nope + vd)), jnp.float32
                           ).reshape(rank, H, nope + vd)
        # the call's own rows alone, causal: a model that is not decoding
        # (the XLA loop on every backend: it is differentiated)
        causal = functools.partial(
            latent_attention.expanded_attention, q, latent, w_kvb,
            jnp.broadcast_to(jnp.arange(L), (B, L)), L, rank=rank,
            nope=nope, scale=scale)
        with jax.named_scope("attn/latent"):
            if not cfg.decode:
                out = causal()
            else:
                out = self._cached(q, latent, w_kvb, token_mask, causal,
                                   scale)
        return dense(cfg.embed_dim, "o_proj")(out.reshape(B, L, H * vd))

    def _cached(self, q, latent, w_kvb, token_mask, causal, scale):
        cfg = self.cfg
        B, L = q.shape[:2]
        T, row = cfg.max_len, cfg.mla_row
        is_initialized = self.has_variable("cache", "cached_latent")
        cl = self.variable("cache", "cached_latent", jnp.zeros,
                           (B, T, row), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((B,), jnp.int32))
        if not is_initialized:      # init trace: shapes only
            return causal()
        idx = ci.value                                           # [B]
        rows = latent_attention.cache_rows(latent, row, cfg.dtype)
        ci.value = idx + L
        if L == 1:
            live = (jnp.ones((B,), bool) if token_mask is None
                    else token_mask[:, 0])
            kernel = latent_attention.applies(L, cfg.mesh, T)
            lengths = jnp.where(live, jnp.minimum(idx + 1, T), 0)
            q_lat = latent_attention.absorb(q[:, 0], w_kvb,
                                            nope=cfg.mla_nope_dim, width=row)
            if kernel:
                cl.value = latent_attention.latent_append(
                    cl.value, rows[:, 0], idx, live)
                o_lat = latent_attention.latent_attend(
                    q_lat, cl.value, lengths, scale=scale)
            else:
                cl.value = latent_attention.latent_append_reference(
                    cl.value, rows[:, 0], idx, live)
                o_lat = latent_attention.latent_attend_reference(
                    q_lat, cl.value, lengths, scale=scale)
            # positions this read fetched of the slots' rows, for the
            # engine's ``latent_tokens_read``
            self.sow("intermediates", "latent_tokens_read",
                     latent_attention.tokens_fetched(
                         lengths, row, T, cfg.dtype, kernel))
            return latent_attention.unabsorb(
                o_lat, w_kvb, rank=cfg.mla_rank, nope=cfg.mla_nope_dim
            )[:, None].astype(q.dtype)
        # contiguous rows at a batch-uniform index (a prefill, a chunk,
        # a reuse suffix: ``Block._decode_attention``'s contract)
        cl.value = jax.lax.dynamic_update_slice(cl.value, rows,
                                                (0, idx[0], 0))
        attend = (latent_attention.latent_expand_tiled if cfg.mla_tiled(L)
                  else latent_attention.expanded_attention)
        # the slot's rows up to the call's last position, and no further
        return attend(q, cl.value, w_kvb, idx[:, None] + jnp.arange(L),
                      idx.max() + L, rank=cfg.mla_rank,
                      nope=cfg.mla_nope_dim, scale=scale)


class Block(nn.Module):
    """One decoder layer; instances are stacked by ``nn.scan`` where
    every layer is alike.  ``layer`` is the layer's place in the
    configuration's plan (``cfg.attn_kind`` / ``cfg.mlp_kind``): a
    stack whose layers differ is unrolled, each ``Block`` its own."""

    cfg: TransformerConfig
    layer: int = 0

    def _ring_attention(self, q, k, v, token_mask=None):
        """A window layer's incremental attention: the cache is a RING
        of ``cfg.ring_len`` positions, position p at ring slot
        ``p % ring``, so a slot's state is O(window) whatever
        ``max_len`` is.  ``cache_index`` stays the absolute position
        (every layer's agrees: the engine reads any).

        The queries attend the ring as it was BEFORE this call, masked
        by the position each ring slot holds (``< window`` back, and
        written at all), and the call's own keys under the banded
        causal mask: one softmax over both.  Then the ring takes the
        call's last ``ring`` REAL tokens: ``token_mask`` ([B, L], real
        tokens leading) keeps a padded prefill's pads, and a free
        slot's ballast token, out of it - a pad written at position
        ``true_len + j`` would replace position ``true_len + j - ring``,
        which the window still needs.  Rotation is applied before the
        cache, at absolute positions, so ring order never matters.

        One-token steps on the chip take the same two Pallas kernels as
        the slabs (``ops/decode_attention``): append at ``index %
        ring``, attend the ring under its circular window.  The einsums
        here are every other call's path and the kernels' parity
        reference, as in :meth:`_decode_attention`."""
        cfg = self.cfg
        W = cfg.attn_window
        B, L, H, Dh = q.shape
        Hk = k.shape[2]
        R = cfg.ring_len
        is_initialized = self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (B, Hk, Dh, R), cfg.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (B, Hk, R, Dh), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((B,), jnp.int32))
        if not is_initialized:      # init trace: shapes only
            return dot_product_attention(q, k, v, causal=True, impl="dense",
                                         window=W,
                                         sm_scale=cfg.sm_scale)
        idx = ci.value                                    # [B]
        if decode_attention.applies(L, cfg.mesh, R):
            live = (jnp.ones((B,), bool) if token_mask is None
                    else token_mask[:, 0])
            ck.value, cv.value = decode_attention.decode_append(
                ck.value, cv.value, k[:, 0], v[:, 0], idx % R, live,
                ring=True)
            ci.value = idx + 1
            return decode_attention.decode_attend(
                q[:, 0], ck.value, cv.value,
                jnp.where(live, jnp.minimum(idx + 1, R), 0),
                newest=idx % R,
                visible=jnp.where(live, jnp.minimum(idx + 1, W), 0),
                scale=cfg.softmax_scale)[:, None]
        slot = jnp.arange(R)[None, :]                     # [1, R]
        # the position ring slot r holds: the latest p < idx, p % R == r
        held = (idx[:, None] - 1) - (idx[:, None] - 1 - slot) % R
        q_pos = idx[:, None] + jnp.arange(L)              # [B, L]
        back = q_pos[:, :, None] - held[:, None, :]       # [B, L, R]
        ring_mask = (held[:, None, :] >= 0) & (back < W)
        i = jnp.arange(L)
        own_mask = (i[None, :] <= i[:, None]) & (i[:, None] - i[None, :] < W)
        seen = jnp.concatenate(
            [ring_mask, jnp.broadcast_to(own_mask, (B, L, L))], axis=-1)
        # the ring and the call's own keys as one operand in the slabs'
        # layouts, and the slab path's precision recipe over it
        k_all = jnp.concatenate(
            [ck.value, k.transpose(0, 2, 3, 1).astype(cfg.dtype)], axis=-1)
        v_all = jnp.concatenate(
            [cv.value, v.transpose(0, 2, 1, 3).astype(cfg.dtype)], axis=2)
        out = self._masked_attention(q, k_all, v_all, seen,
                                     cfg.softmax_scale)
        # the ring takes the call's last R real tokens: slot r the
        # latest real position p in [idx, idx + n_real) with p % R == r
        n_real = (jnp.full((B,), L, jnp.int32) if token_mask is None
                  else token_mask.sum(-1).astype(jnp.int32))
        last = (idx + n_real - 1)[:, None]
        src = last - (last - slot) % R - idx[:, None]     # [B, R]
        take = src >= 0
        at = jnp.clip(src, 0, L - 1)[:, :, None, None]
        k_new = jnp.take_along_axis(k.astype(cfg.dtype), at, axis=1)
        v_new = jnp.take_along_axis(v.astype(cfg.dtype), at, axis=1)
        ck.value = jnp.where(take[:, None, None, :],
                             k_new.transpose(0, 2, 3, 1), ck.value)
        cv.value = jnp.where(take[:, None, :, None],
                             v_new.transpose(0, 2, 1, 3), cv.value)
        ci.value = idx + L
        return out

    def _decode_attention(self, q, k, v, token_mask=None):
        """Incremental attention against a persistent KV cache.  First
        call (init, or a fresh "cache" collection) creates the zeroed
        cache; subsequent mutable-apply calls append the new k/v at
        ``cache_index`` and attend the queries against the whole written
        prefix (the position mask also excludes the not-yet-written
        tail).

        ``cache_index`` is a PER-EXAMPLE ``[B]`` vector so examples in
        one decode batch may sit at different sequence positions — the
        contract continuous batching needs (serving/engine.py): each
        slot advances independently, and the mask is computed per
        example.  Single-token steps (L == 1) scatter each example's
        new k/v at its own index.  Multi-token calls default to one
        contiguous dynamic-update slab, which requires a UNIFORM index
        across the batch — generate()/the engine always prefill from a
        fresh cache at index 0, which satisfies this.  With
        ``cfg.decode_scatter`` multi-token calls instead scatter each
        example's L new entries at ITS OWN index (speculative-decode
        verify: every slot checks k+1 candidates from a different
        position), dropping out-of-bounds rows.

        Cache layouts match the two attention matmuls exactly — keys
        ``[B, Hk, D, max_len]`` (contraction over D, time on the lane
        axis) and values ``[B, Hk, max_len, D]`` — so the einsums need
        no transpose of their own.  Under GQA (``num_kv_heads <
        num_heads``) the cache holds only the Hk K/V heads — decode
        streams the cache every step, so the cache shrinks (and decode
        speeds up) by the group factor — and the query heads attend in
        groups of ``G = H // Hk`` (q head h uses kv head h // G).

        Single-token steps take one of two paths, by what can be
        observed here (``ops/decode_attention.applies``: a TPU, no
        mesh, a lane-tiled ``max_len``) and by no setting.  On the
        chip, two Pallas kernels append and attend IN PLACE, in the
        layout the jit boundary has, and read each slot only up to its
        length; slots that ``token_mask`` marks free (``[B, 1]`` bool)
        are neither written nor read, and their output is zeros.
        Everywhere else, and for every multi-token call, the scatter
        and the einsums below run: the off-chip path and the kernels'
        parity reference.  Compiled for a TPU that path reads both
        slabs WHOLE every step under the position mask, and XLA wants
        them time-major for the scatter, so it copies every slab at the
        program's entry and again at its exit (PERF.md section 6,
        PR 27): the reason the kernels exist.  ``token_mask`` is not
        used there: free slots write and read ballast, as they always
        did.

        A multi-token call whose dense scores ``[H, L, max_len]`` could
        not exist (``ops/decode_attention.prefix_tiled``, from the
        shapes alone) writes its rows the same way and then attends the
        slot's LIVE PREFIX in tiles, one softmax carried across them
        (``prefix_chunk_attention``, scope ``attn/prefix_chunk``)."""
        cfg = self.cfg
        B, L, H, Dh = q.shape
        Hk = k.shape[2]
        is_initialized = self.has_variable("cache", "cached_key")
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (B, Hk, Dh, cfg.max_len), cfg.dtype)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (B, Hk, cfg.max_len, Dh), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((B,), jnp.int32))
        if not is_initialized:      # init trace: shapes only
            return dot_product_attention(q, k, v, causal=True, impl="dense",
                                         sm_scale=cfg.sm_scale)
        idx = ci.value                                    # [B]
        if cfg.is_pass(L):
            return self._pass_attention(q, k, v, token_mask, ck, cv, ci)
        if decode_attention.applies(L, cfg.mesh, cfg.max_len):
            live = (jnp.ones((B,), bool) if token_mask is None
                    else token_mask[:, 0])
            ck.value, cv.value = decode_attention.decode_append(
                ck.value, cv.value, k[:, 0], v[:, 0], idx, live)
            ci.value = idx + 1
            lengths = jnp.where(live, jnp.minimum(idx + 1, cfg.max_len), 0)
            return decode_attention.decode_attend(
                q[:, 0], ck.value, cv.value, lengths,
                scale=cfg.softmax_scale)[:, None]
        if L == 1:
            # per-example scatter (tiny update: B×Hk×D elements)
            ck.value = ck.value.at[jnp.arange(B), :, :, idx].set(
                k[:, 0].astype(cfg.dtype))
            cv.value = cv.value.at[jnp.arange(B), :, idx, :].set(
                v[:, 0].astype(cfg.dtype))
        elif cfg.decode_scatter:
            # per-example multi-token scatter: each example's L new
            # entries land at ITS OWN index (spec-decode verify feeds
            # k+1 candidates per slot at heterogeneous positions).
            # Advanced indices sit at non-adjacent dims, so the update
            # operand's dims come to the front — [B, L, Hk, Dh], which
            # is exactly k/v's layout.  mode="drop": a lane
            # speculating past the cache tail must not write at all
            # (clamping would overwrite the final live position).
            pos = idx[:, None] + jnp.arange(L)            # [B, L]
            bi = jnp.arange(B)[:, None]
            ck.value = ck.value.at[bi, :, :, pos].set(
                k.astype(cfg.dtype), mode="drop")
            cv.value = cv.value.at[bi, :, pos, :].set(
                v.astype(cfg.dtype), mode="drop")
        else:
            # contiguous slab at a batch-uniform index (see docstring)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.transpose(0, 2, 3, 1).astype(cfg.dtype),
                (0, 0, 0, idx[0]))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.transpose(0, 2, 1, 3).astype(cfg.dtype),
                (0, 0, idx[0], 0))
        ci.value = idx + L
        q_pos = idx[:, None] + jnp.arange(L)              # [B, L]
        if cfg.block_length:
            # block-causal: a query sees up to its own block's last row
            q_pos = (q_pos // cfg.block_length + 1) * cfg.block_length - 1
        if decode_attention.prefix_tiled(L, H, cfg.max_len):
            # dense scores that fit nowhere: the live prefix in tiles
            return decode_attention.prefix_chunk_attention(
                q, ck.value, cv.value, q_pos, jnp.max(idx) + L,
                scale=cfg.softmax_scale)
        mask = (jnp.arange(cfg.max_len)[None, None, :]
                <= q_pos[:, :, None])                     # [B, L, max]
        return self._masked_attention(q, ck.value, cv.value, mask,
                                      cfg.softmax_scale)

    def _pass_attention(self, q, k, v, token_mask, ck, cv, ci):
        """A block pass: ``2 L`` rows a slot (``L = cfg.block_length``),
        a COMMIT half and an OPEN half, each live or dead as a whole
        (``token_mask``'s first row of the half), or ``L`` rows, the
        open half alone (a pass in which no slot commits: the commit
        half's kernels and rows are not in the program at all, where a
        dead half still costs its calls).  The commit half, live
        in a slot that commits in this pass, is the slot's finished
        block, written at the index and attended over ``[0, index +
        L)``: it never sees the open half.  The open half is the slot's
        open block: at the index, or behind a live commit half at
        ``index + L`` (the next block), attended up to its own end.  A
        dead half is neither written nor read.  The index comes back at
        the open half's first row: ``L`` further where a block
        committed.

        On the chip each half goes through the one-token kernels as a
        pass of ``L`` rows always has: ``block_append`` (one aliased tile
        of each slab), then the one-token kernel's walk with ``L * G``
        query rows a KV head under the name ``block_attend``; a dead
        half has length 0 and reads nothing.  No query of a block is
        masked from another, so no mask enters the kernel.  Everywhere
        else the scatter and the einsums under the block-causal mask."""
        cfg = self.cfg
        B, S, H, Dh = q.shape
        Hk, L = k.shape[2], cfg.block_length
        idx = ci.value
        live = jnp.ones((B, S), bool) if token_mask is None else token_mask
        # the open half is the call's last L rows
        starts, lives = (idx,), (live[:, 0],)
        if S == 2 * L:
            starts += (idx + jnp.where(live[:, 0], L, 0),)
            lives += (live[:, L],)
        ci.value = starts[-1]
        halves = [(slice(h * L, (h + 1) * L), at, on)
                  for h, (at, on) in enumerate(zip(starts, lives))]
        if decode_attention.block_applies(L, cfg.mesh, cfg.max_len,
                                          cfg.dtype):
            G = H // Hk
            outs = []
            with jax.named_scope("attn/block_pass"):
                for rows, at, on in halves:
                    ck.value, cv.value = decode_attention.block_append(
                        ck.value, cv.value, k[:, rows], v[:, rows], at, on)
                for rows, at, on in halves:
                    lengths = jnp.where(
                        on, jnp.minimum(at + L, cfg.max_len), 0)
                    out = decode_attention.decode_attend(
                        q[:, rows].reshape(B, L, Hk, G, Dh)
                        .transpose(0, 2, 1, 3, 4).reshape(B, Hk * L * G, Dh),
                        ck.value, cv.value, lengths,
                        scale=cfg.softmax_scale, name="block_attend")
                    outs.append(out.reshape(B, Hk, L, G, Dh)
                                .transpose(0, 2, 1, 3, 4)
                                .reshape(B, L, H, Dh))
            return jnp.concatenate(outs, axis=1)
        pos = jnp.concatenate([at[:, None] + jnp.arange(L)
                               for _, at, _ in halves], axis=1)   # [B, S]
        # a dead row lands past the slab and is dropped
        to = jnp.where(live, pos, cfg.max_len)
        bi = jnp.arange(B)[:, None]
        ck.value = ck.value.at[bi, :, :, to].set(k.astype(cfg.dtype),
                                                 mode="drop")
        cv.value = cv.value.at[bi, :, to, :].set(v.astype(cfg.dtype),
                                                 mode="drop")
        # block-causal: a query sees up to its own block's last row
        mask = (jnp.arange(cfg.max_len)[None, None, :]
                <= ((pos // L + 1) * L - 1)[:, :, None])
        return self._masked_attention(q, ck.value, cv.value, mask,
                                      cfg.softmax_scale)

    @staticmethod
    def _masked_attention(q, keys, values, mask, scale):
        """``q [B, L, H, D]`` over keys ``[B, Hk, D, K]`` and values ``[B,
        Hk, K, D]`` in the slabs' layouts under ``mask [B, L, K]``,
        grouped (query head h reads head ``h // (H // Hk)``).  The
        precision recipe matches dense_attention exactly (input-dtype
        matmuls, f32 softmax) so cached decode stays bit-identical to
        the full-prefix forward in bf16 too."""
        B, L, H, D = q.shape
        Hk = keys.shape[1]
        qg = q.reshape(B, L, Hk, H // Hk, D)
        logits = jnp.einsum("blhgd,bhdk->bhglk", qg, keys
                            ).astype(jnp.float32) * scale
        logits = jnp.where(mask[:, None, None], logits, -jnp.inf)
        weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        return jnp.einsum("bhglk,bhkd->blhgd", weights, values
                          ).reshape(B, L, H, D)

    def _attention(self, y, positions, token_mask, kind, shared=None):
        """The attention mixer on the normed input: projections, norms,
        rotation, the layer's kind of attention, the output matrix.
        Returns ``(out, shared)``: a "global" layer of a plan with cross
        layers LENDS its keys and values (``shared["kv"]``: in a decode
        model its slabs as this call left them, else the call's own
        rows), a "cross" layer projects a query only and reads them."""
        cfg = self.cfg
        H, Dh = cfg.num_heads, cfg.head_dim
        Hk = cfg.kv_heads
        assert H % Hk == 0, f"num_heads {H} not divisible by kv heads {Hk}"
        window = cfg.attn_window if kind == "window" else 0
        B, L = y.shape[:2]
        k = v = None
        if kind == "cross":
            q = nn.DenseGeneral((H * Dh,), use_bias=cfg.attn_bias,
                                dtype=cfg.dtype, param_dtype=jnp.float32,
                                name="attn_q")(y)
        else:
            qkv = nn.DenseGeneral(((H + 2 * Hk) * Dh,),
                                  use_bias=cfg.attn_bias,
                                  dtype=cfg.dtype, param_dtype=jnp.float32,
                                  name="attn_qkv")(y)
            qkv = _pin(cfg, qkv, "batch", "seq", "heads")
            q, k, v = jnp.split(qkv, [H * Dh, (H + Hk) * Dh], axis=-1)
        if cfg.qk_norm and cfg.qk_norm_per_head:
            # each head over its own head_dim, one scale for all heads
            q = RMSNorm(cfg.dtype, cfg.norm_eps, name="q_norm")(
                q.reshape(B, L, H, Dh)).astype(cfg.dtype)
            k = RMSNorm(cfg.dtype, cfg.norm_eps, name="k_norm")(
                k.reshape(B, L, Hk, Dh)).astype(cfg.dtype)
        elif cfg.qk_norm:
            # over the whole projection, before the split into heads;
            # back in the compute dtype (the f32 scale promotes)
            q = RMSNorm(cfg.dtype, cfg.norm_eps, name="q_norm")(q).astype(
                cfg.dtype)
            k = RMSNorm(cfg.dtype, cfg.norm_eps, name="k_norm")(k).astype(
                cfg.dtype)
        q = q.reshape(B, L, H, Dh)
        rotate = cfg.rope_window if window else cfg.rope_global
        if rotate:
            q = rope(q, positions, cfg.rope_theta)
        if k is not None:
            k, v = k.reshape(B, L, Hk, Dh), v.reshape(B, L, Hk, Dh)
            if rotate:
                k = rope(k, positions, cfg.rope_theta)
        if cfg.diff_attn:
            q = self._diff_queries(q)
            if k is not None:
                k = k.reshape(B, L, Hk // 2, 2 * Dh)
                v = v.reshape(B, L, Hk // 2, 2 * Dh)
        lends = (shared is not None and kind == "global"
                 and "cross" in cfg.layer_attn)
        slabs = cfg.decode and self.has_variable("cache", "cached_key")
        # the scopes name a mixed stack's kinds in a trace; a stack
        # without a window keeps the op names it always had
        with (jax.named_scope(f"attn/{kind}")
              if cfg.attn_window or "ssm" in cfg.layer_attn
              else contextlib.nullcontext()):
            if kind == "cross":
                attn = self._cross_attention(q, positions, token_mask,
                                             shared["kv"])
            elif cfg.decode and window:
                attn = self._ring_attention(q, k, v, token_mask)
            elif cfg.decode:
                attn = self._decode_attention(q, k, v, token_mask)
            elif cfg.block_length:
                # block-causal over the call's own positions: the dense
                # path under the mask (the kernels are causal)
                blk = positions // cfg.block_length           # [B, L]
                attn = dot_product_attention(
                    q, k, v, causal=False, impl="dense",
                    mask=(blk[:, None, None, :] <= blk[:, None, :, None]),
                    sm_scale=cfg.sm_scale)
            else:
                # GQA is handled by the dispatch: dense attends grouped
                # K/V without materialising repeats; kernels expand inside
                attn = dot_product_attention(q, k, v, causal=True,
                                             impl=cfg.attention_impl,
                                             mesh=cfg.mesh, window=window,
                                             sm_scale=cfg.sm_scale)
        if lends:
            shared = {**shared, "kv": (
                ("slab", self.get_variable("cache", "cached_key"),
                 self.get_variable("cache", "cached_value")) if slabs
                else ("call", k, v))}
        if cfg.diff_attn:
            attn = self._diff_combine(attn)
        attn = _pin(cfg, attn.reshape(B, L, H * Dh), "batch", "seq", "heads")
        if cfg.attn_gate and kind == "global":
            with jax.named_scope("attn/out_gate"):
                gate = nn.DenseGeneral((H * Dh,), use_bias=False,
                                       dtype=cfg.dtype,
                                       param_dtype=jnp.float32,
                                       name="attn_gate")(y)
                attn = (attn.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))).astype(cfg.dtype)
        return nn.DenseGeneral(cfg.embed_dim, use_bias=cfg.attn_bias,
                               dtype=cfg.dtype, param_dtype=jnp.float32,
                               name="attn_out")(attn), shared

    def _diff_queries(self, q):
        """Differential attention on the plain paths.  Query pair p
        (heads 2p, 2p + 1) reads key / value pair r = p // 2 (heads 2r,
        2r + 1), head s of a query pair against key s of the pair, and
        BOTH value heads.  With a pair of keys side by side as one key
        of twice the width (and so the values), and each query beside
        zeros in the half its key does not lie in, that is grouped
        attention of H queries over Hk / 2 keys, four to a key: ``[B, L,
        H, Dh] -> [B, L, H, 2 Dh]``.  The cache holds the same bytes,
        and rows of 128 where the published head is 64."""
        B, L, H, Dh = q.shape
        half = jax.nn.one_hot(jnp.arange(H) % 2, 2, dtype=q.dtype)   # [H, 2]
        return (q[:, :, :, None, :] * half[None, None, :, :, None]
                ).reshape(B, L, H, 2 * Dh)

    def _diff_combine(self, attn):
        """``[B, L, H, 2 Dh]`` (each head's softmax over BOTH values of
        its pair) to the differential output ``[B, L, H, Dh]``: pair p
        gives ``(head 2p) - lambda * (head 2p + 1)``, through an RMSNorm
        over the pair's 2 Dh with one scale a layer, times ``1 -
        lambda_init``; heads 2p and 2p + 1 take its halves.  ``lambda =
        exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init
        = 0.8 - 0.6 exp(-0.3 layer)``."""
        cfg = self.cfg
        B, L, H, D2 = attn.shape
        with jax.named_scope("attn/diff_combine"):
            lam, lam0 = self._lambda(D2 // 2)
            pair = attn.astype(jnp.float32).reshape(B, L, H // 2, 2, D2)
            out = pair[:, :, :, 0] - lam * pair[:, :, :, 1]
            out = RMSNorm(cfg.dtype, 1e-5, name="subln")(out) * (1.0 - lam0)
            return out.astype(cfg.dtype).reshape(B, L, H, D2 // 2)

    def _lambda(self, width: int):
        """``(lambda, lambda_init)`` of this layer's differential
        attention: four learned vectors of ``width``."""
        lam0 = 0.8 - 0.6 * math.exp(-0.3 * self.layer)
        lq1, lk1, lq2, lk2 = (
            self.param(name, nn.initializers.normal(0.1), (width,),
                       jnp.float32)
            for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
        return (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                + lam0), lam0

    def _cross_attention(self, q, positions, token_mask, lent):
        """Attention of this layer's queries over keys and values ANOTHER
        layer wrote (``lent``: ``("slab", keys [B, Hk, D, max_len],
        values [B, Hk, max_len, D])`` of a decode model, the lender's
        slabs with this call's rows already in them, or ``("call", k,
        v)``: the call's own rows).  A query at position p sees rows 0
        .. p.  Nothing is written.  One row a lane on the chip is the
        slabs' own kernel (``ops/decode_attention.decode_attend``), which
        reads live positions of live slots; everything else the einsums
        of :meth:`_decode_attention`."""
        cfg = self.cfg
        how, k, v = lent
        B, L, H, D = q.shape
        with jax.named_scope("attn/cross"):
            if how == "call":
                return dot_product_attention(q, k, v, causal=True,
                                             impl="dense",
                                             sm_scale=cfg.softmax_scale)
            Hk, T = k.shape[1], k.shape[-1]
            kernel = decode_attention.applies(L, cfg.mesh, T)
            if L == 1:
                live = (jnp.ones((B,), bool) if token_mask is None
                        else token_mask[:, 0])
                lengths = jnp.where(
                    live, jnp.minimum(positions[:, 0] + 1, T), 0)
                # positions this read fetched of the lender's rows (the
                # kernel: the blocks its walk lists, whole, so each live
                # slot's rows up to the end of its last block and nothing
                # of a free slot; the einsums: every slot's slab), for the
                # engine's ``borrowed_kv_tokens_read``
                self.sow("intermediates", "borrowed_rows_read",
                         decode_attention.tokens_fetched(
                             lengths, Hk, D, T, k.dtype, kernel))
            if kernel:
                return decode_attention.decode_attend(
                    q[:, 0], k, v, lengths, scale=cfg.softmax_scale)[:, None]
            mask = (jnp.arange(T)[None, None, :]
                    <= positions[:, :, None])                 # [B, L, max]
            return self._masked_attention(q, k, v, mask, cfg.softmax_scale)

    @nn.compact
    def __call__(self, x, positions, token_mask=None, snap_at=None,
                 shared=None):
        """``(x, aux)``; with ``shared`` (what crosses layers in a plan
        that has such layers: ``memory``, a Mamba-1 layer's scan output,
        and ``kv``, a global layer's keys and values) ``(x, aux,
        shared)``."""
        cfg = self.cfg
        kind = cfg.attn_kind(self.layer)
        x = _pin(cfg, x, "batch", "seq", None)
        y = _norm(cfg, "attn_norm")(x)
        if kind == "ssm":
            mixed = Mamba2Mixer(cfg, name="ssm")(y, token_mask, snap_at)
        elif kind == "kda":
            mixed = KDAMixer(cfg, name="kda")(y, token_mask, snap_at)
        elif kind == "latent":
            mixed = LatentAttention(cfg, name="mla")(y, positions, token_mask)
        elif kind == "mamba1":
            mixed, memory = Mamba1Mixer(cfg, name="ssm")(y, token_mask,
                                                         snap_at)
            if shared is not None:
                shared = {**shared, "memory": memory}
        elif kind == "gmu":
            mixed = GatedMemoryUnit(cfg, name="gmu")(y, shared["memory"])
        else:
            mixed, shared = self._attention(y, positions, token_mask, kind,
                                            shared)
        if cfg.post_norms:
            with jax.named_scope("attn/post_norm"):
                mixed = RMSNorm(cfg.dtype, cfg.norm_eps,
                                name="attn_post_norm")(mixed).astype(cfg.dtype)
        x = _pin(cfg, _residual(cfg, x, mixed), "batch", "seq", None)
        y = _norm(cfg, "mlp_norm")(x)
        if cfg.mlp_kind(self.layer) == "sparse":
            from edl_tpu.ops.moe import MoEMLP
            y, aux = MoEMLP(num_experts=cfg.moe_experts,
                            mlp_dim=cfg.expert_dim, top_k=cfg.moe_top_k,
                            capacity_factor=cfg.moe_capacity,
                            dtype=cfg.dtype, decode=cfg.decode,
                            gated=cfg.moe_gated,
                            norm_topk=cfg.moe_norm_topk,
                            router=cfg.moe_router,
                            select_bias=cfg.moe_select_bias,
                            routed_scale=cfg.moe_routed_scale,
                            shared_dim=cfg.moe_shared_dim,
                            held=cfg.moe_held, mesh=cfg.mesh,
                            pass_tokens=cfg.pass_tokens,
                            name="moe")(y, token_mask)
            out = (_pin(cfg, _residual(cfg, x, self._post_mlp(y)),
                        "batch", "seq", None), aux)
            return out if shared is None else (*out, shared)
        gate = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.dtype,
                        param_dtype=jnp.float32, name="mlp_gate")(y)
        up = nn.Dense(cfg.mlp_dim, use_bias=False, dtype=cfg.dtype,
                      param_dtype=jnp.float32, name="mlp_in")(y)
        y = nn.silu(_pin(cfg, gate, "batch", "seq", "mlp")) * _pin(
            cfg, up, "batch", "seq", "mlp")
        x = _residual(cfg, x, self._post_mlp(nn.Dense(
            cfg.embed_dim, use_bias=False, dtype=cfg.dtype,
            param_dtype=jnp.float32, name="mlp_out")(y)))
        out = (_pin(cfg, x, "batch", "seq", None), None)
        return out if shared is None else (*out, shared)

    def _post_mlp(self, y):
        """The MLP branch's output through its sandwich norm, where the
        configuration has one."""
        cfg = self.cfg
        if not cfg.post_norms:
            return y
        with jax.named_scope("mlp/post_norm"):
            return RMSNorm(cfg.dtype, cfg.norm_eps,
                           name="mlp_post_norm")(y).astype(cfg.dtype)


# What a remat block keeps for its backward pass: the matmuls' outputs,
# and what the splash forward kernel hands its backward (``out`` and the
# logsumexp: a Pallas call is no dot, and without its name the backward
# pass would run that kernel again).  One object: a jaxpr prints its
# policy, and two traces of one model must read the same.
_REMAT_KEEPS = jax.checkpoint_policies.save_from_both_policies(
    jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    jax.checkpoint_policies.save_only_these_names(SPLASH_RESIDUALS))


def _remat(block):
    return nn.remat(block, prevent_cse=False, policy=_REMAT_KEEPS)


class TransformerLM(nn.Module):
    cfg: TransformerConfig

    @nn.compact
    def __call__(self, ids, positions=None, train: bool = True,
                 return_hidden: bool = False, with_aux: bool = False,
                 token_mask=None, snap_at=None, last_at=None,
                 tail: bool = True):
        """Logits [B, L, V] f32 — or, with ``return_hidden``, the
        final-norm hidden states [B, L, D] for the fused-CE loss path
        (:func:`lm_loss_fused`), which never materialises the logits.
        ``with_aux`` additionally returns the mean per-layer auxiliary
        loss (the MoE load-balance term; 0 for dense MLP configs).
        ``token_mask`` ([B, L] bool) marks real tokens in a padded
        batch — pad positions are excluded from MoE routing (they must
        not consume expert capacity; ops/moe.py compute_routing); in a
        decode model real tokens lead, and a state-space layer's state
        stops at the last of them.  ``snap_at`` ([B] int, decode models)
        is handed to the layers that keep a recurrence (``Mamba2Mixer``,
        ``KDAMixer``, ``Mamba1Mixer``).

        The LAST-POSITION CUT (decode models whose stack ends in layers
        that keep nothing a position, ``cfg.tail_start``): a multi-token
        call that samples at one row a lane says which (``last_at`` [B]
        int, an index into the call), and the tail runs on that row
        alone: ``x`` and the memory gathered there after the last layer
        below the tail, the cross layers' one query over the rows the
        call has just written, logits ``[B, 1, V]``.  Exact: nothing
        above the tail reads another row's tail output.  ``tail=False``
        (a prefill chunk that samples nothing) leaves the tail out and
        returns the hidden rows below it.

        A BLOCK PASS (a call of ``cfg.pass_tokens`` = ``2 *
        block_length`` positions of a ``decode_scatter`` model, or of
        ``block_length``, the open half alone: ``cfg.is_pass``,
        ``Block._pass_attention``) returns its open half's logits, ``[B,
        block_length, V]``: nobody samples a block that is committed."""
        cfg = self.cfg
        del train
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape)
        x = nn.Embed(cfg.vocab_size, cfg.embed_dim, param_dtype=jnp.float32,
                     dtype=cfg.dtype, name="tok_embed")(ids)
        if cfg.embed_multiplier != 1.0:
            x = x * jnp.asarray(cfg.embed_multiplier, x.dtype)
        x = _pin(cfg, x, "batch", "seq", None)

        if cfg.decode:
            # unrolled layers with SEPARATE per-layer caches: inside the
            # token-generation while-loop XLA aliases each [B, H, D, max]
            # cache buffer in place.  The scanned (stacked) layout forced
            # a full copy of the 12-layer cache tensor per decoded token
            # — measured 10ms/step of pure copy at the flagship config.
            # generate() splits the trained stacked params to match
            # (models/generate.py _split_layer_params).
            aux = None
            shared = {} if cfg.shares else None
            for i in range(cfg.num_layers):
                if i == cfg.tail_start and i:
                    if not tail:
                        return x
                    if last_at is not None and x.shape[1] > 1:
                        at = last_at.astype(jnp.int32)[:, None]
                        x = jnp.take_along_axis(x, at[..., None], axis=1)
                        shared = {**shared, "memory": jnp.take_along_axis(
                            shared["memory"], at[..., None], axis=1)}
                        positions = jnp.take_along_axis(positions, at, axis=1)
                        token_mask = (None if token_mask is None else
                                      jnp.take_along_axis(token_mask, at,
                                                          axis=1))
                x, _, *rest = Block(cfg, i, name=f"layer_{i}")(
                    x, positions, token_mask, snap_at, shared)
                shared = rest[0] if rest else None
        elif not cfg.uniform:
            # layers that differ cannot be stacked (a dense layer has
            # no expert matrices): unrolled, ``layer_<i>`` parameters,
            # the layout the decode model has.  What crosses layers
            # (``cfg.shares``) is not threaded through remat
            block = (_remat(Block) if cfg.remat and not cfg.shares
                     else Block)
            auxes, shared = [], ({} if cfg.shares else None)
            for i in range(cfg.num_layers):
                x, a, *rest = block(cfg, i, name=f"layer_{i}")(
                    x, positions, token_mask, None, shared)
                shared = rest[0] if rest else None
                if a is not None:
                    auxes.append(a)
            aux = jnp.stack(auxes) if auxes else None
        else:
            block = _remat(Block) if cfg.remat else Block
            Stack = nn.scan(block, variable_axes={"params": 0, "cache": 0},
                            split_rngs={"params": True},
                            length=cfg.num_layers,
                            in_axes=nn.broadcast, metadata_params={},
                            unroll=1 if cfg.scan_layers else cfg.num_layers)
            x, aux = Stack(cfg, name="layers")(x, positions, token_mask)
        if 1 < cfg.pass_tokens == x.shape[1]:
            # a block pass of both halves samples its open half alone
            x = x[:, cfg.block_length:]
        x = _pin(cfg, _norm(cfg, "final_norm")(x), "batch", "seq", None)
        aux_total = (jnp.mean(aux) if aux is not None
                     else jnp.zeros((), jnp.float32))
        if return_hidden:
            # NOTE: init() must run with the default return_hidden=False
            # so the lm_head params are created; apply() with extra
            # params present is fine in flax
            return (x, aux_total) if with_aux else x
        if cfg.tie_embeddings:
            embed = self.get_variable("params", "tok_embed")["embedding"]
            logits = x @ embed.T.astype(cfg.dtype)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                              param_dtype=jnp.float32, name="lm_head")(x)
        logits = logits.astype(jnp.float32)
        if cfg.logits_scaling != 1.0:
            logits = logits / cfg.logits_scaling
        return (logits, aux_total) if with_aux else logits


def _masked_mean(nll, mask):
    if mask is not None:
        return (nll * mask).sum() / jnp.maximum(mask.sum(), 1)
    return nll.mean()


def lm_loss(logits, targets, mask=None):
    """Next-token cross entropy; ``targets`` already shifted."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return _masked_mean(nll, mask)


def _head_is_whole(cfg: TransformerConfig) -> bool:
    """Whether the head's ``[D, V]`` weight lies whole on every device: no
    mesh, or a mesh none of whose axes splits ``embed`` or ``vocab``
    (``_fences_norms``' rule, for the head)."""
    return cfg.mesh is None or logical_sharding(
        ("embed", "vocab"), cfg.mesh).is_fully_replicated


def lm_loss_fused(params, hidden, targets, cfg: TransformerConfig,
                  mask=None, block_size: int = 4096):
    """Next-token CE from ``apply(..., return_hidden=True)`` hidden
    states: ``lm_loss`` of the dense head (the same bf16-cast matmul with
    f32 accumulation, f32 softmax) without its ``[B, L, V]`` logits, by
    one of the two paths of ``edl_tpu/ops/ce.py``.

    Where the head's weight lies whole on every device (``_head_is_whole``:
    no mesh, a mesh of one, a mesh that splits the batch or the sequence
    alone) one sweep over blocks of tokens, which under differentiation
    forms the gradients while a block's logits are at hand: three
    head-sized matmuls a step, and a whole float32 ``[D, V]`` gradient
    resident beside the head.  Where the head is split (``fsdp``, ``tp``)
    the loop over blocks of ``block_size`` columns of the vocabulary,
    which never holds the head whole and pays for that with a fourth
    matmul, the logits computed again in the backward.  The sweep took
    33 ms off a 491 ms step on one v5e chip (PERF.md section 6, PR 51).
    ``block_size`` is the loop's alone; the sweep sizes its own blocks."""
    from edl_tpu.ops.ce import blockwise_cross_entropy, sweep_cross_entropy

    if cfg.tie_embeddings:
        w = params["tok_embed"]["embedding"].T
    else:
        w = params["lm_head"]["kernel"]
    if cfg.logits_scaling != 1.0:
        # on the small operand, in float32: the logits are the dense
        # head's divided by logits_scaling
        hidden = (hidden.astype(jnp.float32) / cfg.logits_scaling).astype(
            hidden.dtype)
    w = w.astype(hidden.dtype)
    if _head_is_whole(cfg):
        # the mean's weights are known before the sweep, so the sweep's
        # cotangent is one number and its backward only scales
        if mask is None:
            token_weight = jnp.full(targets.shape, 1.0 / targets.size)
        else:
            token_weight = mask / jnp.maximum(mask.sum(), 1)
        return sweep_cross_entropy(hidden, w, targets, token_weight,
                                   mesh=cfg.mesh)
    nll = blockwise_cross_entropy(hidden, w, targets, block_size=block_size)
    return _masked_mean(nll, mask)
