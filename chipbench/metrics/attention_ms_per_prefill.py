"""Device milliseconds per prefill (``jit_prefill``) in ops whose
innermost model scope is ``attention``: projections, rotary embedding,
scores, the Pallas softmax and the cache write of every layer."""

from chipbench import scopes


def read(v):
    return scopes.scope_ms_per_run(v.trace, "jit_prefill", "attention")
