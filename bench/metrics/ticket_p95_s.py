"""95th-percentile client-side ticket latency over every ticket of the
window; a failed ticket ranks as late."""

from stats import percentile


def read(run):
    if not run.get("tickets"):
        return None
    return percentile([t["latency_s"] for t in run["tickets"]], 95)
