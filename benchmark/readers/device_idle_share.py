"""The share of the traced slice in which no operation ran on the device:
1 − the union of the device's operation intervals over the slice's length."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
