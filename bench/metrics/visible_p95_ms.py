"""95th percentile over every append of the window, from its scheduled
arrival to the end of the first lookup batch served at a version that
holds it, in ms.  (Appends are a tenth of the requests: at the cell's
rate a window holds about 500, so p95 has some 25 samples beyond it.)"""

import numpy as np


def read(ctx):
    lat = np.asarray(ctx["raw"].get("visible_latency_s", ()), np.float64)
    return None if lat.size == 0 else float(np.percentile(lat, 95) * 1e3)
