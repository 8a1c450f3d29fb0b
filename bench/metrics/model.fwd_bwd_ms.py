"""Device time of the model's forward and backward per step, ms: the step's
ops under the ``loss_grad`` named scope, any scope nested in it included
(forward, remat recompute, backward and the model-axis collectives),
averaged over the chips."""


def read(run):
    tr = run.get("trace")
    if not tr or not tr.get("scope_s"):
        return None
    per = [d["loss_grad"] for d in tr["scope_s"]]
    return 1e3 * sum(per) / len(per) / run["steps"]
