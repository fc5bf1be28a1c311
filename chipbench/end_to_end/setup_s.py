"""Process start to window start (host clock): data, build, warm-up and,
in a run that compiles, compilation."""


def read(run):
    return run.setup_s
