"""Device milliseconds per decode step (``jit_serve_step``) in ops whose
innermost model scope is ``layer_scan``: the layer scan's own slicing of
the stacked weights and KV cache and its restacking of the new cache,
outside any sub-layer's norm, attention or FFN."""

from chipbench import scopes


def read(v):
    return scopes.scope_ms_per_run(v.trace, "jit_serve_step", "layer_scan")
