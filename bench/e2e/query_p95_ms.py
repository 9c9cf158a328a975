"""95th percentile of the latency of every query the window sent and the
service answered, timed as ``query_p50_ms`` times it."""
import numpy as np


def read(w):
    lat = w["latencies_ms"]
    return float(np.percentile(lat, 95)) if len(lat) else None
