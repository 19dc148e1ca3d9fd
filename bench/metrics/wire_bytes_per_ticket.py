"""Bytes the server read and wrote over the window (its ``bytes_in`` and
``bytes_out`` counters) per completed ticket."""


def read(run):
    done = run.get("completed", 0)
    return run["wire_bytes"] / done if done and "wire_bytes" in run \
        else None
