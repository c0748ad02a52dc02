"""The 95th percentile (nearest rank) of request latency, from each
request's due time to the return of the call that answered it, over
every request due in the window."""
import harness as H


def read(run):
    lat = run.samples.get("serve_latency_s")
    if lat is None or len(lat) == 0:
        return None
    return H.nearest_rank(lat, 95) * 1e3
