"""Faults planted in the program under test, to see the check catch them.

Each is a context manager that patches the program where the step produces
the thing it breaks; build the step (``program.Program``) inside it, since
the patches act when the step is traced.

* ``unchanged``: the step returns its state unchanged;
* ``half_batch``: half of each rank's tokens are left out of the loss, the
  mean taken over the rest;
* ``no_exchange``: the data-axis all-reduce is left out, so each rank
  applies its own compressed update;
* ``double_head``: the head's gradient is counted twice where it is
  produced.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

FAULTS = ("unchanged", "half_batch", "no_exchange", "double_head")


@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def planted(fault: str):
    from repro.core import dist, error_feedback
    from repro.models import model

    if fault == "unchanged":
        def apply_updates(compressor, params, grads, state, *a, **k):
            return params, state, {}
        return _patched(error_feedback, "apply_updates", apply_updates)
    if fault == "half_batch":
        loss_fn = model.loss_fn

        def half(params, batch, *a, **k):
            labels = batch["labels"]
            keep = (jnp.arange(labels.size) < labels.size // 2).reshape(
                labels.shape)
            return loss_fn(params, dict(batch, labels=jnp.where(
                keep, labels, -1)), *a, **k)
        return _patched(model, "loss_fn", half)
    if fault == "no_exchange":
        pmean = dist.AxisBackend.pmean

        def local(self, x, axes):
            axes = axes if isinstance(axes, (tuple, list)) else (axes,)
            return x if "data" in axes else pmean(self, x, axes)
        return _patched(dist.AxisBackend, "pmean", local)
    if fault == "double_head":
        apply = error_feedback.apply_updates

        def doubled(compressor, params, grads, *a, **k):
            return apply(compressor, params,
                         dict(grads, head=2 * grads["head"]), *a, **k)
        return _patched(error_feedback, "apply_updates", doubled)
    raise ValueError(f"unknown fault {fault!r}; have {FAULTS}")


def applies(fault: str, data_ranks: int) -> bool:
    return fault != "no_exchange" or data_ranks > 1

