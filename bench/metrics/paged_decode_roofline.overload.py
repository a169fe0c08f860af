"""``paged_decode_roofline`` in a cell above the knee, where the paged
kernel of a full batch moves the output tokens per second."""
from bench.metrics_common import paged_decode_roofline


def read(run):
    return paged_decode_roofline(run)
