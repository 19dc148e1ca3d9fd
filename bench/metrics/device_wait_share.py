"""Share of the executor's wall time spent waiting for survivor fetches
from the device: sum of ``device_wait_s`` over sum of ``total_s`` of the
window's studies (``StreamResult.stats``)."""


def read(run):
    st = [s["stats"] for s in run.get("studies", ())]
    total = sum(s["total_s"] for s in st)
    return sum(s["device_wait_s"] for s in st) / total if total else None
