"""Median executor wall time of a ticket: each result's
``stats["total_s"]`` as the executor recorded it."""

from stats import percentile


def read(run):
    xs = [t["executor_s"] for t in run.get("tickets", ())
          if t["executor_s"] is not None]
    return percentile(xs, 50) if xs else None
