"""Median over the window's completed requests of completion minus
submission, on the client's host clock."""

import statistics


def read(run):
    lat = run.layer.get("latencies")
    if not lat:
        return None
    return statistics.median(lat)
