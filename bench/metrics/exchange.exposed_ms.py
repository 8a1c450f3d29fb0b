"""Exposed exchange time per step, ms: the part of the collective intervals
in which no other operation runs on that chip, averaged over the chips."""


def read(run):
    tr = run.get("trace")
    if not tr or not any(tr["collective_s"]):
        return None
    return 1e3 * sum(tr["exposed_s"]) / len(tr["exposed_s"]) / run["steps"]
