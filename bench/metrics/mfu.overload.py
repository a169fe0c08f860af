"""``mfu`` in a cell above the knee, where the step's share of the peak
moves the output tokens per second."""
from bench.metrics_common import step_mfu


def read(run):
    return step_mfu(run)
