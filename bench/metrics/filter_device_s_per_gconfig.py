"""Device self time of the chunk step's ops under the ``dominance_filter``
scope, summed over devices, per 10^9 configurations (device trace; op
paths from the compiled step's HLO text)."""

import trace_spans


def read(run):
    return trace_spans.readings(run.get("spans"), run).get(
        "filter_device_s_per_gconfig")
