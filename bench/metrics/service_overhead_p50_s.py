"""Median over tickets of client latency minus the ticket's executor
``total_s``: queue wait, plan, journal, encode and wire."""

from stats import percentile


def read(run):
    xs = [t["latency_s"] - t["executor_s"] for t in run.get("tickets", ())
          if t["latency_s"] is not None and t["executor_s"] is not None]
    return percentile(xs, 50) if xs else None
