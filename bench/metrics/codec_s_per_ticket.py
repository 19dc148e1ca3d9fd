"""Seconds of ``repro.wire.encode`` and ``repro.wire.decode`` spans in the
window per completed ticket: the wire codec on both ends (program spans)."""

import trace_spans


def read(run):
    return trace_spans.readings(run.get("spans"), run).get(
        "codec_s_per_ticket")
