"""Mean modeled WAN time before first enqueue (``RequestRecord.wan_s``)
over the window's requests, in milliseconds; 0 for requests that crossed
no link."""


def read(run):
    if not run.rows:
        return None
    return sum(r.wan_s for r in run.rows) / len(run.rows) * 1e3
