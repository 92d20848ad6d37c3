"""Mean state hand-off time of a repartition (``SwitchReport.t_handoff``:
the measured wall plus the priced link seconds), over the repartitions
that moved state."""


def read(run):
    h = [r.t_handoff for c in run.cycles for r in c["reports"]
         if r.handoff_mode in ("transfer", "recompute")]
    return 1e3 * sum(h) / len(h) if h else None
