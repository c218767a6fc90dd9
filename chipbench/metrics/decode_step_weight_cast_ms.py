"""Device milliseconds per decode step (``jit_serve_step``) in ops whose
innermost model scope is ``weight_cast``: the step's cast of every fp32
weight matrix to the compute dtype."""

from chipbench import scopes


def read(v):
    return scopes.scope_ms_per_run(v.trace, "jit_serve_step", "weight_cast")
