"""Roofline share of the Pallas softmax inside the prefill program: the
HBM bytes of each launch's live causal scores (chipbench.work, one launch
per attention layer: every score read in float32, every weight written in
bf16) at the chip's HBM bandwidth, over the launches' summed device time,
in percent.  Bound by bytes: the chip publishes no vector-unit peak."""

from chipbench import work


def read(v):
    n, seconds = v.trace.kernel("softmax_2d", module="jit_prefill")
    if not n:
        return None
    t = v.traffic
    need = n * work.softmax_bytes(v.config, t["batch"], t["prompt_len"])
    return 100.0 * need / v.peak["hbm_bytes_per_s"] / seconds
