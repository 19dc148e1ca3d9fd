"""Whole-window study throughput: every configuration of every study
over the time from the window's start to the end of the last study."""

from stats import whole_window_rate


def read(run):
    if not run.get("studies"):
        return None
    return whole_window_rate(run["studies"], run["window_start"])
