"""Device time per admission: device busy time inside the benchmark's
``admit`` spans (around ``ServingEngine.execute_admit``) in the traced
window, over the number of admissions."""
from chipbench import trace as TR


def read(run):
    return TR.device_ms_per_span(run.trace, "admit")
