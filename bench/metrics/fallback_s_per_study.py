"""Host seconds a study spends re-deriving the chunks that overflowed
the survivor buffer through the dense evaluator: the studies'
``stats["fallback_s"]``, the summed durations of their
``repro.stream.fallback`` spans, over the number of studies."""


def read(run):
    st = [s["stats"] for s in run.get("studies", ())]
    if not st or any("fallback_s" not in s for s in st):
        return None
    return sum(s["fallback_s"] for s in st) / len(st)
