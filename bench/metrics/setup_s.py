"""Process start to the first timed study or ticket: imports, compile or
cache loads, warm-up and server start."""


def read(run):
    return run["setup_s"]
