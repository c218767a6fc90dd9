"""Device program executions per decode step: every program the engine
launched in the traced window (prefill, decode step, the sampling ops, the
cache's allocation), per device, over the tokens sampled per slot."""


def read(v):
    runs, _ = v.trace.module_runs()
    steps = len(v.window.items) * v.traffic["new_tokens"]
    return runs / v.chips / steps if steps else None
