"""Whole-step model FLOP/s utilization, %: the traced window's tokens per
second × model FLOPs per token (``flops_per_token`` of the configuration's
``bench/archs/<arch>.py``) over chips × the bf16 peak (``bench/peaks.py``).
The step runs float32 at default matmul precision, which on this chip is
one bf16 pass, so the bf16 peak is the base."""


def read(run):
    if not run.get("tokens_per_s"):
        return None
    return (100.0 * run["tokens_per_s"] * run["flops_per_token"]
            / (run["chips"] * run["peak_flops"]))
