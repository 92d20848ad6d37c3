"""95th percentile of the latency of every frame served in the window
(arrival to cloud exit on the stream clock)."""
import numpy as np


def read(run):
    lat = [r.latency for c in run.cycles for r in c["timeline"].records
           if r.served]
    return float(np.percentile(lat, 95.0)) * 1e3 if lat else None
