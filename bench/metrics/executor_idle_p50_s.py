"""Median over tickets of the device's idle time inside the ticket's
``repro.stream.grid`` span, mean over the cell's devices (device trace)."""

import trace_spans


def read(run):
    return trace_spans.readings(run.get("spans"), run).get(
        "executor_idle_p50_s")
