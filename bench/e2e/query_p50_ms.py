"""Median latency of every query the window sent and the service answered,
timed from each query's due time (open loop) or its send (closed loop)."""
import numpy as np


def read(w):
    lat = w["latencies_ms"]
    return float(np.percentile(lat, 50)) if len(lat) else None
