"""Device seconds of the chunk-step program, summed over devices, per
10^9 configurations folded in the window.  The backend's jitted
``step`` (``one`` where a dispatch folds a single chunk); its pmapped
form on several devices runs as ``jit_call_wrapped``, the only pmapped
program of a study."""

STEP = ("jit_step", "jit_one", "jit_call_wrapped")


def read(run):
    tr = run.get("trace")
    if tr is None or not run.get("studies"):
        return None
    s = sum(v for k, v in tr["program_s"].items() if k in STEP)
    if s <= 0:
        return None
    return s / (sum(x["n_configs"] for x in run["studies"]) / 1e9)
