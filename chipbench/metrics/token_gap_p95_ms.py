"""95th percentile of the gaps between a session's consecutive tokens:
differences of the stream-clock completion times of the served steps in
which the session was live, over all sessions.  Admission and switch
stalls fall inside these gaps.  A gap across two cycles is not counted:
each cycle has a clock of its own."""
import numpy as np


def gaps(timeline):
    last, out = {}, []
    for r in sorted((r for r in timeline.records if r.served),
                    key=lambda r: r.t_done):
        for sid in r.sessions or ():
            if sid in last:
                out.append(r.t_done - last[sid])
            last[sid] = r.t_done
    return out


def read(run):
    g = [x for c in run.cycles for x in gaps(c["timeline"])]
    return float(np.percentile(g, 95.0)) * 1e3 if g else None
