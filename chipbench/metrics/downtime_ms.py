"""Mean stream-clock length of a repartition window: the measured wall
of the switch plus the priced link time of its state hand-off
(``SwitchWindow.duration``), over every repartition in the window."""


def read(run):
    ws = [w.duration for c in run.cycles for w in c["timeline"].windows]
    return 1e3 * sum(ws) / len(ws) if ws else None
