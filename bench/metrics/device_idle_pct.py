"""Share of the traced window in which no operation ran on the device
(1 minus the union of op intervals over the window, profiler trace)."""


def read(run):
    return None if run.device is None else run.device["idle_pct"]
