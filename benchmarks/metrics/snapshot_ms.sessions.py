"""Layer `cache`: host time a step spends on state snapshots (the
phase `snapshot`: the row copies dispatched at a block boundary and at
admission), over the steps of the sending window."""


def read(run):
    window = run["facts"].get("window")
    if not window or not window.get("steps") \
            or window.get("snapshot_s") is None:
        return None
    return 1e3 * window["snapshot_s"] / window["steps"]
