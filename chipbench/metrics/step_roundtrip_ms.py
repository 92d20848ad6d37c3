"""Host round trips of a decode step: device idle milliseconds inside the
program's ``step.input`` (the token and position uploads) and
``step.commit`` (the token, bounds and logits brought back) spans, per
decode step in the traced window."""
from chipbench import program_spans as P


def read(run):
    p = P.program(run)
    if p is None:
        return None
    steps, trips = p.named("step"), p.named("step.input", "step.commit")
    if not steps or not trips:
        return None
    return P.idle_ns(run.trace, trips) * 1e-6 / len(steps)
