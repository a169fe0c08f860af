"""Share of the window's finished requests served (fused) on the cloud
tier, in percent."""


def read(run):
    if "cloud" not in run.models:
        return None
    done = [r for r in run.rows if r.done]
    if not done:
        return None
    return 100.0 * sum(r.tier == "cloud" for r in done) / len(done)
