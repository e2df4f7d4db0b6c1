"""Process start to the window's first request: data, build, warm-up and
compilation."""


def read(run, suffix):
    return run.setup_s
