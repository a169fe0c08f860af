"""Mean device time of one fused decode block (the engines' jitted
``fused`` program) in the traced window, in milliseconds; read where it
moves the time per output token."""
from bench.metrics_common import decode_block_ms


def read(run):
    return decode_block_ms(run)
