"""Model FLOPs of the prefill and decode work whose calls fell in the
traced window (matrix products of real tokens, attention over the live
context, logits where used) over (window x bf16 peak), in percent; read
where it moves the time per output token."""
from bench.metrics_common import step_mfu


def read(run):
    return step_mfu(run)
