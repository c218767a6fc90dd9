"""Roofline share of the Pallas uniform kernel of sampling: each launch
draws one slot's vocabulary of float32 uniforms (chipbench.work), at the
chip's HBM bandwidth, over the launches' summed device time, in percent."""

from chipbench import work


def read(v):
    n, seconds = v.trace.kernel("uniform_2d")
    if not n:
        return None
    return 100.0 * n * work.uniform_bytes(v.config) / v.peak["hbm_bytes_per_s"] / seconds
