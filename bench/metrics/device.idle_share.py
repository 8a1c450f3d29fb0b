"""Device idle share, %: 1 − (union of the device's operation intervals) /
the traced window, averaged over the chips used."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr["busy_s"]:
        return None
    busy = sum(tr["busy_s"]) / len(tr["busy_s"])
    return 100.0 * (1.0 - busy / tr["window_s"])
