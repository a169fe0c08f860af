"""p95 over every window request with two or more tokens due of
(latency - time to first token) / (tokens - 1), in milliseconds, from the
due time. A request that failed or had not finished when the drain ended
ranks last; if the tail lands on one, its wait by then spread over its
tokens is reported."""
from bench.metrics_common import tpot_tail


def read(run):
    return tpot_tail(run, 95)
