"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals / window)."""
from chipbench import trace as TR


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace.window()
    return 100.0 * (1.0 - TR.busy_ns(run.trace.ops, lo, hi)
                    / run.trace.n_devices / (hi - lo))
