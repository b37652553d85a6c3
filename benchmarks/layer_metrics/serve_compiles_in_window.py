"""Programs JAX lowered inside the window (jaxpr_to_mlir_module events,
cache hit or miss alike).  Should be 0: every shape is warmed in
set-up."""


def read(ctx):
    return ctx["counters"].get("compiles_in_window")
