"""Mean build time of a repartition's pipeline (``SwitchReport.t_build``,
the program's stopwatch around the strategy's build)."""


def read(run):
    b = [r.t_build for c in run.cycles for r in c["reports"]]
    return 1e3 * sum(b) / len(b) if b else None
