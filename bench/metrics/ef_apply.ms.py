"""Device time of the error-feedback update per step, ms: the step's ops
under the ``ef_apply`` named scope and not under the ``compress`` scope
nested in it (weight decay, delta = gradient + error, the new error,
momentum and the parameter write), averaged over the chips.  A trace's
scope times include the scopes nested in each."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("scope_s"):
        return None
    per = [d["ef_apply"] - d["compress"] for d in tr["scope_s"]]
    return 1e3 * sum(per) / len(per) / run["steps"]
