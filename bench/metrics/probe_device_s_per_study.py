"""Device seconds of the dense-evaluator program (``jit(evalfn)``, the
probe of each study and the survivor-overflow fallback) per study."""

PROBE = ("jit_evalfn",)


def read(run):
    tr = run.get("trace")
    if tr is None or not run.get("studies"):
        return None
    s = sum(v for k, v in tr["program_s"].items() if k in PROBE)
    return s / len(run["studies"]) if s > 0 else None
