"""delivery_p90_ms: 90th percentile, over every delivery of a window step,
from the producer entering close() of the file to the consumer instance
holding its analysis result on the host: the staleness the scientist sees."""

import statistics


def read(r):
    lat = [(d["t"] - r.run.close_enter[d["step"]]) * 1e3
           for d in r.window_deliveries() if d["step"] in r.run.close_enter]
    if len(lat) < 10:
        return None
    return statistics.quantiles(lat, n=10, method="inclusive")[8]
