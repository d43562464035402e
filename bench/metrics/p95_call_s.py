"""p95_call_s: the 95th percentile of the seconds of every call in the
window, each ending on the device (host clock)."""
import statistics


def read(run):
    lat = run["latencies"]
    if run.get("trace") is not None or len(lat) < 20:
        return None
    return statistics.quantiles(lat, n=20, method="inclusive")[18]
