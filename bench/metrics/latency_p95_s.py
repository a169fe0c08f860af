"""p95 request completion latency over every request due in the window,
from its due time (the program's ``latency_s``, downlink included). A
request that failed or had not finished when the drain ended counts as
infinite; if the tail lands on one, the time it had waited by then is
reported."""
from bench.metrics_common import tail


def read(run):
    return tail(run, "latency_s", 95)
