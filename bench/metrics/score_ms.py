"""Mean host time of one scoring call of the modality-aware module over
the window (the scheduler's ``score_time_s`` / ``n_scored``)."""


def read(run):
    c0, c1 = run.out.c0, run.out.c1
    n = c1["n_scored"] - c0["n_scored"]
    if n <= 0:
        return None
    return (c1["score_time_s"] - c0["score_time_s"]) / n * 1e3
