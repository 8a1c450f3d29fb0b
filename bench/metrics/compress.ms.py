"""Device time of compression per step, ms: the step's ops under the
``compress`` named scope and not under the ``exchange`` scope nested in it
(payload build, projection, orthogonalization, back-projection, aggregate
and reconstruction), averaged over the chips.  A trace's scope times
include the scopes nested in each."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("scope_s"):
        return None
    per = [d["compress"] - d["exchange"] for d in tr["scope_s"]]
    return 1e3 * sum(per) / len(per) / run["steps"]
