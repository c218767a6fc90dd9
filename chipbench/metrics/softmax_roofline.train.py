"""Roofline share of the Pallas softmax inside the training step (its
forward launches, recomputed ones included; the backward is plain XLA):
each launch's live causal score bytes (chipbench.work) at the chip's HBM
bandwidth over the launches' summed device time, in percent."""

from chipbench import work


def read(v):
    n, seconds = v.trace.kernel("softmax_2d", module="jit_train_step")
    if not n:
        return None
    t = v.traffic
    need = n * work.softmax_bytes(v.config, t["batch"], t["seq_len"])
    return 100.0 * need / v.peak["hbm_bytes_per_s"] / seconds
