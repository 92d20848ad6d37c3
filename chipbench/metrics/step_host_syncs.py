"""Blocking device-to-host waits per decode step: the program's
``host_sync`` counter (each ``block_until_ready`` and each device array
brought to the host) inside its ``step`` spans, per step in the traced
window."""
from chipbench import program_spans as P


def read(run):
    return P.host_syncs_per_step(run)
