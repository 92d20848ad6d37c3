"""Device time per served decode step: device busy time inside the
benchmark's ``step`` spans (around the pipeline's ``process``) in the
traced window, over the number of steps."""
from chipbench import trace as TR


def read(run):
    return TR.device_ms_per_span(run.trace, "step")
