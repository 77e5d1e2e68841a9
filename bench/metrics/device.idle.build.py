"""device.idle.build: the share of the traced window, in %, in which no
operation ran on the device: 1 - busy / window (bench/trace.py)."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share()
