"""Collective time during which no other operation ran on that device, over
the traced window, averaged over the chips."""


def read(run):
    tr = run.get("trace")
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
