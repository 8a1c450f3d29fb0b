"""Device time of the exchange's collectives per step, ms: the union of
all-reduce / all-gather / reduce-scatter / collective-permute / all-to-all
operation intervals in the trace, per step, averaged over the chips."""


def read(run):
    tr = run.get("trace")
    if not tr or not any(tr["collective_s"]):
        return None
    return 1e3 * sum(tr["collective_s"]) / len(tr["collective_s"]) / run["steps"]
