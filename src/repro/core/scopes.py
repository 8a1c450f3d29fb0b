"""Named scopes of the training step, and which compiled op each one owns.

The step puts its work under four ``jax.named_scope`` names:

* ``loss_grad`` — forward, remat recompute, backward and the model-axis
  (tensor-parallel) collectives (``launch/train.py``);
* ``ef_apply`` — the error-feedback update: weight decay, Δ = g + e,
  e = Δ − recon, momentum and the parameter write
  (``core/error_feedback.apply_updates``);
* ``compress`` — the compressor inside it: payload build, projection,
  orthogonalization, back-projection, aggregate and reconstruction;
* ``exchange`` — each data-axis collective entry of
  :class:`~repro.core.dist.MeshCtx` that :class:`~repro.core.dist.
  CollectiveStats` records, its wire cast and pack/unpack included.

Nesting is exchange ⊂ compress ⊂ ef_apply.  A scope only extends JAX's name
stack, so it changes the ``op_name`` metadata of the HLO instructions and
nothing XLA compiles.  :func:`op_scopes` reads a compiled module's HLO text
(``compiled.as_text()``) back into instruction name → innermost scope, the
names a device trace gives its ops.
"""

from __future__ import annotations

import functools
import re
from typing import Dict

import jax

LOSS_GRAD = "loss_grad"
COMPRESS = "compress"
EXCHANGE = "exchange"
EF_APPLY = "ef_apply"
SCOPES = (LOSS_GRAD, COMPRESS, EXCHANGE, EF_APPLY)


def scoped(name: str):
    """Decorator: each call runs under a fresh ``jax.named_scope(name)``.
    (``jax.named_scope`` used as a decorator keeps one context object for
    every call, which a nested or concurrent call would overwrite.)"""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


# a computation's header, an instruction of it, and what an instruction calls:
# ``ROOT %fusion.3 = f32[8]{0} fusion(...), calls=%fused_computation.3,
# metadata={op_name="a/b" ...}``
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")
# a transformation around a name-stack component: ``transpose(jvp(x))``
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _component(name: str) -> str:
    """``loss_grad`` from ``transpose(jvp(loss_grad))``: the scope a
    path component names once JAX's transformation wrappers are peeled."""
    m = _WRAPPED.match(name)
    while m:
        name = m.group(1)
        m = _WRAPPED.match(name)
    return name


def scope_of(op_name: str):
    """The innermost of :data:`SCOPES` among the whole components of an
    ``op_name`` path, or ``None``."""
    for part in reversed(op_name.split("/")):
        part = _component(part)
        if part in SCOPES:
            return part
    return None


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name → innermost scope, for every instruction of every
    computation in ``hlo_text`` (fusion bodies and while bodies too).

    An instruction's own ``op_name`` path decides.  One whose path names no
    scope, or that has none (XLA makes some instructions without metadata),
    takes the scope of the root of the computation it calls (a fusion goes
    with its root), else that of the instruction that calls its own
    computation (a loop body goes with its loop).  Instructions with no
    scope either way are left out."""
    own, calls, comp_of, root, caller = {}, {}, {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            comp = c.group(1) if c else comp
            continue
        name = m.group(2)
        o = _OP_NAME.search(line)
        own[name] = scope_of(o.group(1)) if o else None
        comp_of[name] = comp
        if m.group(1):
            root[comp] = name
        called = _CALLS.findall(line)
        for branches in _BRANCHES.findall(line):
            called += [b.strip().lstrip("%") for b in branches.split(",")]
        calls[name] = called
        for c in called:
            caller.setdefault(c, name)

    out: Dict[str, str] = {}

    def resolve(name, seen):
        if name in out or own[name] is not None:
            return out.get(name, own[name])
        nxt = [root[c] for c in calls[name] if c in root]
        nxt.append(caller.get(comp_of[name]))
        for n in nxt:
            if n is not None and n not in seen:
                scope = resolve(n, seen | {n})
                if scope is not None:
                    return scope
        return None

    for name in own:
        scope = resolve(name, {name})
        if scope is not None:
            out[name] = scope
    return out


def module_name(hlo_text: str):
    """The module's name (``jit_local_step``) from its ``HloModule`` line."""
    for line in hlo_text.splitlines():
        m = _MODULE.match(line)
        if m:
            return m.group(1)
    return None
