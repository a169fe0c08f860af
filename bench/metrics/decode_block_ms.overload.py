"""``decode_block_ms`` in a cell above the knee, where a full batch's
block sets the output tokens per second."""
from bench.metrics_common import decode_block_ms


def read(run):
    return decode_block_ms(run)
