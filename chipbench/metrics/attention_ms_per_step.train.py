"""Device milliseconds per training step (``jit_train_step``) in ops
whose innermost model scope is ``attention``: its forward, its remat
recompute and its backward, which carry the forward's scope."""

from chipbench import scopes


def read(v):
    return scopes.scope_ms_per_run(v.trace, "jit_train_step", "attention")
