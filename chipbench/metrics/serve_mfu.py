"""Model FLOPs of the traced window's requests (chipbench.work: 2N per
token plus attention over the live context) over the seconds in which the
device ran an op in that window, over the chips' bf16 peak, in percent:
the device's own utilisation, with the host's idle gaps left to
``device_idle_share.serve``."""

from chipbench import work


def read(v):
    t = v.traffic
    flops = len(v.window.items) * work.serve_request_flops(
        v.config, t["batch"], t["prompt_len"], t["new_tokens"])
    return 100.0 * flops / v.trace.busy_s() / (v.chips * v.peak["bf16_flops_per_s"])
