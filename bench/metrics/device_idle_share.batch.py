"""Share of the traced window in which no operation ran on the device:
one minus the union of op intervals over the window, averaged over the
devices the cell uses."""


def read(run):
    tr = run.get("trace")
    return None if tr is None else tr["idle_share"]
