"""Weight copy of a repartition's build: wall milliseconds of the
program's ``build.weights`` spans (a new container's own weights, through
host memory), per repartition (``engine.switch``) in the traced window."""
from chipbench import program_spans as P


def read(run):
    p = P.program(run)
    if p is None:
        return None
    switches, copies = p.named("engine.switch"), p.named("build.weights")
    if not switches or not copies:
        return None
    return sum(s.wall_ns for s in copies) * 1e-6 / len(switches)
