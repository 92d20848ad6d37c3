"""Host work of an admission: device idle milliseconds inside the
program's ``sessions.admit`` spans (prefill dispatch, slot placement and
host copies, a parked slot), per admission in the traced window."""
from chipbench import program_spans as P


def read(run):
    p = P.program(run)
    if p is None:
        return None
    admits = p.named("sessions.admit")
    if not admits:
        return None
    return P.idle_ns(run.trace, admits) * 1e-6 / len(admits)
