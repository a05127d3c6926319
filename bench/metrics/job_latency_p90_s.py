"""job_latency_p90_s: 90th percentile, over every job expressed in the
window, of the wall time from expressing it to its result being
fetched."""

from bench.common import quantile


def read(record, trace):
    lat = record.get("job_latency_s")
    return quantile(lat, 0.9) if lat else None
