"""Tokens decoded for live sessions in the window (one per live session
per served step), over the window's wall seconds."""


def read(run):
    toks = sum(len(r.sessions or ()) for c in run.cycles
               for r in c["timeline"].records if r.served)
    return toks / run.window_s if toks else None
