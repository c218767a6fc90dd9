"""Share of the traced window in which the device was idle inside the
engine's ``serve.sample`` spans (the host's sampling of each token: the
per-slot uniform launches, the Gumbel noise and the argmax), in percent;
None where the trace holds no such span."""

from chipbench import scopes


def read(v):
    if not isinstance(v.trace, scopes.ScopedTrace) or not v.trace.program_spans("serve.sample"):
        return None
    return 100.0 * v.trace.idle_s("serve.sample") / v.trace.window_s()
