"""95th percentile of latency over every request due in the window, from
its due time to the wall-clock end of its batch."""
import numpy as np

from bench.metrics import latencies_s


def read(run):
    lat = latencies_s(run)
    return float(np.percentile(lat, 95)) * 1e3 if lat else None
