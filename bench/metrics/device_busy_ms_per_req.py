"""Device busy time of the traced window over the requests that finished
in it, in milliseconds: the device work one served request costs."""


def read(run):
    tr = run.out.trace
    if tr is None:
        return None
    lo, hi = run.out.trace_t
    n = sum(1 for r in run.out.rows
            if r.done and lo <= r.due + r.latency_s <= hi)
    return tr["busy_s"] * 1e3 / n if n else None
