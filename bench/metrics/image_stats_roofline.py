"""Least time to read the pixels of every image scored in the traced
window from HBM over the summed device time of the image-statistics
kernel, percent."""
from bench import flops
from bench.metrics_common import IMAGE_KERNEL, in_trace, op_time


def read(run):
    n, t = op_time(run, IMAGE_KERNEL)
    if not n or t <= 0:
        return None
    need = sum(flops.image_bytes(*w[2][-2:]) for w in run.out.work
               if w[0] == "score" and in_trace(run, w[1]))
    if need == 0:
        return None
    return 100.0 * need / run.peak["hbm_bytes_per_s"] / t
