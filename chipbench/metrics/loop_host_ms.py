"""The engine's own loop: device idle milliseconds inside the program's
``engine.run`` spans and outside every span opened directly inside them
(requests, switches, admissions, controller ticks, drains), per served
step in the traced window."""
from chipbench import program_spans as P


def read(run):
    p = P.program(run)
    if p is None:
        return None
    loops, steps = p.named("engine.run"), p.named("step")
    if not loops or not steps:
        return None
    kids = [c for s in loops for c in p.children.get(s.id, ())]
    return (P.idle_ns(run.trace, loops) - P.idle_ns(run.trace, kids)) \
        * 1e-6 / len(steps)
