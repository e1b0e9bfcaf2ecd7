"""Share of the traced slice in which no operation ran on the device, %."""


def read(run):
    if run.trace is None or run.trace.idle_share is None:
        return None
    return 100.0 * run.trace.idle_share
