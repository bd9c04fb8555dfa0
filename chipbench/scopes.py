"""Which part of the training step a device op belongs to, from the
named scopes the program writes into the step's HLO metadata.

The profiler's op events carry only the HLO instruction (``%fusion.928 =
...``), so the map from instruction to ``op_name`` comes from the
optimized HLO text of the step that ran (:func:`op_names`,
:func:`train_step_op_names`).  :func:`classify` is the one rule for
every reading of these classes:

- ``recompute``: the forward recomputed in the backward, which
  ``jax.checkpoint`` names ``rematted_computation``, and the clones
  XLA's own rematerialization adds (instruction names ending
  ``.remat<n>``);
- ``backward``: under ``transpose(`` (JAX's name for the backward of
  the program's ``forward`` scope);
- ``forward``: under the program's ``forward`` scope;
- ``optimizer``: under the program's ``optimizer`` scope (clip, AdamW,
  the gradient norm);
- ``unscoped``: everything else (scan and cond plumbing, gradient
  accumulation, copies, collectives XLA adds without metadata).

``attention`` (the program's scope around the attention call) lies
across these: attention ops count in their pass too.
"""
from __future__ import annotations

import re
from typing import Dict, List

CLASSES = ("forward", "backward", "recompute", "optimizer", "unscoped")

# one instruction of HLO text with its op_name metadata
_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*?metadata=\{[^\n]*?'
    r'op_name="((?:[^"\\]|\\.)*)"', re.MULTILINE)
_XLA_REMAT = re.compile(r"\.remat\d*$")


def _scope(name: str) -> re.Pattern:
    # a whole name-stack component, bare or inside a transform's parens
    return re.compile(rf"(?:^|[/(;]){name}(?:$|[/);])")


_FORWARD = _scope("forward")
_OPTIMIZER = _scope("optimizer")


def op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name → ``op_name`` metadata, from an HLO module's
    text (``compiled.as_text()``); instructions without one are left
    out."""
    return {m.group(1): m.group(2) for m in _INSTR.finditer(hlo_text)}


def instruction(event_name: str) -> str:
    """The instruction an op event names: ``%fusion.928 = (...) ...`` →
    ``fusion.928``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def classify(instr: str, op_name: str) -> str:
    """One of :data:`CLASSES` for an instruction and its ``op_name``."""
    if "rematted_computation" in op_name or _XLA_REMAT.search(instr):
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if _FORWARD.search(op_name):
        return "forward"
    if _OPTIMIZER.search(op_name):
        return "optimizer"
    return "unscoped"


def components(op_name: str) -> List[str]:
    """The name-stack components of an ``op_name`` in order, bare or
    inside a transform's parens: ``a/transpose(jvp(forward))/dot`` →
    ``[a, transpose, jvp, forward, dot]``.  A scope covers an op where
    its name is one of them (``attention`` covers
    ``a/checkpoint/attention/dot``, not ``a/dot_product_attention``)."""
    return [c for c in re.split(r"[/();]", op_name) if c]


def train_step_op_names(engine, stacked_batch) -> Dict[str, str]:
    """The op-name map of the step ``engine`` (a ``PhaseEngine``) runs
    for chunks shaped like ``stacked_batch``.  Lowered from argument
    shapes, the step's module is the one that was dispatched, so JAX's
    persistent compilation cache returns the executable that ran rather
    than compiling it again."""
    import jax
    leaves = jax.tree.leaves(stacked_batch)
    k, batch_size = leaves[0].shape[:2]
    step = engine.compiled_step(batch_size, k, stacked_batch)
    structs = engine._arg_structs(batch_size, k, stacked_batch)
    with engine.mesh_context():
        return op_names(step.lower(*structs).compile().as_text())
