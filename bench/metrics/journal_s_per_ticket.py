"""Seconds of ``repro.service.journal`` spans in the window per completed
ticket: the service's durable state writes (program spans)."""

import trace_spans


def read(run):
    return trace_spans.readings(run.get("spans"), run).get(
        "journal_s_per_ticket")
