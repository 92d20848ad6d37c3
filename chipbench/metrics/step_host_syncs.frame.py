"""Blocking device-to-host waits per served frame: the program's
``host_sync`` counter inside its ``step`` spans (one per frame), per
frame in the traced window."""
from chipbench import program_spans as P


def read(run):
    return P.host_syncs_per_step(run)
