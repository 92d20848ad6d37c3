"""Device time per served frame: device busy time inside the benchmark's
``frame`` spans (around the pipeline's ``process``: edge and cloud stage
executables) in the traced window, over the number of frames."""
from chipbench import trace as TR


def read(run):
    return TR.device_ms_per_span(run.trace, "frame")
