"""Process start to the first timed request: data, session, compiles or
cache loads, warm-up (host clock)."""


def read(run):
    return run.setup_s
