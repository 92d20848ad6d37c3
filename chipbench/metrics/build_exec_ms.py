"""Executables of a repartition's build: wall milliseconds covered by the
program's ``build.exec`` spans (retrace, compile or load from the
persistent cache, of each stage; the edge stage may build beside the
cloud one, so their union is counted), per repartition
(``engine.switch``) in the traced window."""
from chipbench import program_spans as P
from chipbench import trace as TR


def read(run):
    p = P.program(run)
    if p is None:
        return None
    switches, execs = p.named("engine.switch"), p.named("build.exec")
    if not switches or not execs:
        return None
    covered = sum(b - a for a, b in TR.union((s.start, s.end)
                                             for s in execs))
    return covered * 1e-6 / len(switches)
