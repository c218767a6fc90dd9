"""Forward and backward model FLOPs per step (chipbench.work, the PaLM
formula, recomputation not counted) times the steps of the traced window,
over the seconds in which the device ran an op in that window, over the
chips' bf16 peak, in percent: the device's own utilisation, with the
host's idle gaps left to ``device_idle_share.train``."""

from chipbench import work


def read(v):
    t = v.traffic
    flops = len(v.window.items) * work.train_step_flops(v.config, t["batch"], t["seq_len"])
    return 100.0 * flops / v.trace.busy_s() / (v.chips * v.peak["bf16_flops_per_s"])
