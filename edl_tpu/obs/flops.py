"""FLOP accounting for MFU: the peak-TFLOPS table, the XLA
cost-analysis FLOP count and the analytic transformer FLOP formula
behind the trainer's live gauges.  Four helpers:

- :func:`peak_tflops` — bf16 peak per chip from the device kind
  (longest-match against :data:`PEAK_TFLOPS`; ``EDL_TPU_PEAK_TFLOPS``
  overrides — the only way to get an MFU on CPU or an unknown kind);
- :func:`xla_cost_flops` — the compiled computation's total FLOPs from
  XLA's cost analysis (the whole module, all devices), ``None`` when
  the backend can't answer.  Caveat: a model running layers under
  ``lax.scan`` counts the loop body ONCE — use the analytic count for
  those (a scanned LM step read 0.70 "TFLOP" vs ~7 real);
- :func:`analytic_lm_flops_per_token` — the PaLM-appendix transformer
  accounting (6·N matmul params + 6·layers·seq·d_model causal
  attention per token) from four sizes, dense MHA only;
- :func:`config_flops_per_token` - the same accounting from a
  ``TransformerConfig``: GQA widths, and for an expert configuration
  the ACTIVE parameters (router + ``moe_top_k`` gated or ungated
  experts a token), not all of them.

``mfu = achieved_tflops / peak_tflops``; the trainer's ``edl_mfu`` /
``edl_tflops_per_chip`` gauges (``train/trainer.py``) compute it
through here.
"""

from __future__ import annotations

import os

# bf16 peak TFLOP/s per chip by device kind (public spec sheets);
# extend as kinds appear.  Used only for the optional MFU estimate.
PEAK_TFLOPS = {
    "TPU v4": 275, "TPU v5": 459, "TPU v5p": 459,
    "TPU v5 lite": 197, "TPU v5e": 197, "TPU v6e": 918, "TPU v6 lite": 918,
}


def peak_tflops(device) -> float | None:
    """Known bf16 peak for ``device`` (a jax Device), or None.
    ``EDL_TPU_PEAK_TFLOPS`` overrides unconditionally."""
    env = os.environ.get("EDL_TPU_PEAK_TFLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            return None  # malformed override: MFU absent, never a crash
    kind = getattr(device, "device_kind", "")
    # LONGEST match wins: "TPU v5 lite" (197) must not be swallowed by
    # the "TPU v5" prefix (459, the v5p number) — the r03 MFU was
    # understated 2.3× by exactly that (0.131 reported vs 0.306 real)
    best = None
    for name, peak in PEAK_TFLOPS.items():
        if (kind.startswith(name) or name in kind) and (
                best is None or len(name) > len(best[0])):
            best = (name, peak)
    return float(best[1]) if best else None


def xla_cost_flops(jitted, *args) -> float | None:
    """Total FLOPs of one execution of ``jitted(*args)`` from XLA's
    compiled cost analysis (global — across every device the
    computation spans), or None when the backend offers no analysis /
    reports zero.  The AOT ``lower().compile()`` path does NOT share
    the jit dispatch cache: this is a FULL recompile (~0.9 s measured
    on a toy model) even when ``jitted`` has already run with these
    shapes.  Never call it on a hot path — background it the way
    ``train/trainer.py``'s ``_compute_flops`` thread does."""
    try:
        cost = jitted.lower(*args).compile().cost_analysis()
        cost = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops = float(cost.get("flops", 0.0))
        return flops if flops > 0 else None
    # edl-lint: disable=wire-error — optional enrichment: MFU simply
    # stays absent when the backend offers no cost analysis
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        return None


def analytic_lm_flops_per_token(num_layers: int, embed_dim: int,
                                mlp_dim: int, vocab_size: int,
                                seq: int) -> float:
    """Analytic train FLOPs per token for the decoder-only transformer:
    6·N for the matmul params (embed table excluded — lookup, not
    matmul; lm_head kept — it IS a matmul) + causal-attention
    6·layers·seq·d_model.  Use this instead of :func:`xla_cost_flops`
    for scan-over-layers models, where cost analysis counts the loop
    body once instead of ×num_layers."""
    n_matmul = (num_layers * (4 * embed_dim ** 2           # qkv + out proj
                              + 3 * embed_dim * mlp_dim)   # swiglu mlp
                + embed_dim * vocab_size)                  # lm head
    return float(6 * n_matmul + 6 * num_layers * seq * embed_dim)


def config_flops_per_token(cfg, seq: int) -> float:
    """Analytic train FLOPs per token of a ``TransformerConfig``:
    6 per matmul parameter a token takes part in
    (``transformer.active_matmul_params``: attention at its GQA widths,
    the head, and the MLP - for an expert configuration the router and
    the ``moe_top_k`` experts a token is routed to, three matrices each
    when gated) plus causal attention, 6 * layers * seq * heads *
    head_dim.  Equals :func:`analytic_lm_flops_per_token` for a dense
    MHA configuration."""
    from edl_tpu.models.transformer import active_matmul_params

    attn = 6 * cfg.num_layers * seq * cfg.num_heads * cfg.head_dim
    return float(6 * active_matmul_params(cfg) + attn)
