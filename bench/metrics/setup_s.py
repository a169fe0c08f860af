"""Set-up: seconds from process start to the start of the window (loading,
making weights, compiling or loading every program, the ramp)."""


def read(run):
    return run.out.setup_s
