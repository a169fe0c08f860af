"""Least time the paged decode attention of the traced window needs (the
live keys and values of every decode step at its context length, against
HBM bandwidth, or its operations against the bf16 peak, whichever is
larger) over the summed device time of the paged decode kernel, percent;
read where it moves the time per output token."""
from bench.metrics_common import paged_decode_roofline


def read(run):
    return paged_decode_roofline(run)
