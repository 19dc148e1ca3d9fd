"""Median time a ticket waited in the service queue: the ``queued_s`` of the
window's ``repro.service.start`` spans (program spans)."""

import trace_spans


def read(run):
    return trace_spans.readings(run.get("spans"), run).get(
        "queue_wait_p50_s")
