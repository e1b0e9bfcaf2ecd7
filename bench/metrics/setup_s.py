"""Process start to the first request."""


def read(run):
    return run.setup["setup_s"]
