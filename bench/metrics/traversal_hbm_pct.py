"""The least bytes the window's answers need moved on the device, over the
device's busy time and its peak HBM bandwidth (peaks.json)."""
from bench.readings import least_bytes


def read(run):
    if run.device is None or run.peaks is None or run.device["busy_s"] <= 0:
        return None
    need = least_bytes(run)
    if need <= 0:
        return None
    return 100.0 * need / run.device["busy_s"] / run.peaks["hbm_bytes_per_s"]
