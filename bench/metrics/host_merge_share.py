"""Share of the executor's wall time spent merging survivors into the
host front: sum of ``host_merge_s`` over sum of ``total_s``."""


def read(run):
    st = [s["stats"] for s in run.get("studies", ())]
    total = sum(s["total_s"] for s in st)
    return sum(s["host_merge_s"] for s in st) / total if total else None
