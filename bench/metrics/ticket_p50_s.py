"""Median client-side ticket latency, from when each ticket was due to
be sent to when its result returned; a failed ticket ranks as late."""

from stats import percentile


def read(run):
    if not run.get("tickets"):
        return None
    return percentile([t["latency_s"] for t in run["tickets"]], 50)
