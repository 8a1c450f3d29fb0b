"""Device time of the data-axis exchange per step, ms: the step's ops under
the ``exchange`` named scope (wire cast, pack, the collective, unpack),
averaged over the chips; none where no op runs under it."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("scope_s"):
        return None
    per = [d["exchange"] for d in tr["scope_s"]]
    if not any(per):
        return None
    return 1e3 * sum(per) / len(per) / run["steps"]
