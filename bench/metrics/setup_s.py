"""Set-up: from process start to the first timed request (JAX start-up,
graph generation, drawing the jobs, staging, loading or compiling the
programs, the warm-up job)."""


def read(ctx):
    return ctx["setup_s"]
