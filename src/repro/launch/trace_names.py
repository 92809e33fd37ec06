"""The names the training path gives its time on the profiler's clock.

Device scopes (``jax.named_scope``) mark the layers of the jitted train
step. A scope is a prefix of the ``op_name`` metadata of every HLO op
traced under it, forward and backward: ``jit(train_step)/jvp(loss)/...``
in the forward pass, ``jit(train_step)/transpose(jvp(loss))/...`` in the
backward, ``jit(train_step)/adamw/...`` outside differentiation.

  ``embed``      embedding lookup and its dropout
  ``phase_a``    per recurrent layer, the mask schedules and the
                 time-batched non-recurrent matmul before the scan
  ``attention``  Luong attention outside a fused decoder scan
  ``output``     output dropout and the vocabulary projection
  ``loss``       log-softmax, NLL and the (masked) mean
  ``grad_clip``  clipping the gradient to its global norm
  ``adamw``      the AdamW update and its application to the parameters
                 (``optim.apply_updates``, which every optimizer shares)
  ``grad_psum``  the data-parallel psums of the loss, weights and grads

The recurrence has no scope: the nested jit that runs it names it in
every op_name, ``jit(cell_scan)`` or ``jit(_decoder_scan)``
(``RECURRENCE``), on the xla and the Pallas path alike.

The rule: no scope may enclose a call that reaches a ``pallas_call``,
directly or through a caller (around ``value_and_grad``, the whole loss,
``lstm_stack``, or inside a scan around its kernel). A scope around a
differentiated nested jit renames the call: ``jvp(jit(cell_scan))``
becomes ``jvp(<scope>)/jit(cell_scan)`` in the op_name, and the
instruction ``jvp_jit_cell_scan__`` becomes ``jit_cell_scan_``, which
are the names a kernel's op_name and trace events are matched by. So a
site whose impl is "pallas" (the compact gather matmuls) takes no scope
while that impl is selected (``scope(name, impl)``).

Host spans (``jax.profiler.TraceAnnotation``) mark the phases of each
step of ``launch/train.py``'s loop, under a ``StepTraceAnnotation``
named ``STEP`` that carries the step number: ``SPANS``, in the order the
loop enters them.
"""
from __future__ import annotations

import contextlib

import jax

EMBED = "embed"
PHASE_A = "phase_a"
ATTENTION = "attention"
OUTPUT = "output"
LOSS = "loss"
GRAD_CLIP = "grad_clip"
ADAMW = "adamw"
GRAD_PSUM = "grad_psum"
SCOPES = (EMBED, PHASE_A, ATTENTION, OUTPUT, LOSS, GRAD_CLIP, ADAMW,
          GRAD_PSUM)
RECURRENCE = ("jit(cell_scan)", "jit(_decoder_scan)")

STEP = "train"
BATCH = "train.batch"          # the batch's leaves to the device
ARGS = "train.args"            # the step index and the dropout key
DISPATCH = "train.dispatch"    # the jitted step's dispatch
SYNC = "train.sync"            # waiting on float(loss)
CKPT = "train.ckpt"            # the checkpoint, where one is due
SPANS = (BATCH, ARGS, DISPATCH, SYNC, CKPT)


def layer(op_name: str):
    """The layer an HLO op's ``op_name`` names: a scope of ``SCOPES``,
    ``"recurrence"`` for a scan's nested jit, or None (unscoped). Only a
    name directly under the jitted step (or its data-parallel
    ``shard_map``) counts, with its ``jvp(`` and ``transpose(`` wrappers
    taken off: ``jit(train_step)/transpose(jvp(loss))/...`` is ``loss``,
    ``jit(train_step)/jvp(x)/jit(cell_scan)/...`` is None."""
    parts = op_name.split("/")
    if parts[1:2] == ["shard_map"]:
        parts = parts[1:]
    if len(parts) < 2 or not parts[0].startswith(("jit(", "shard_map")):
        return None
    head = parts[1]
    while head.startswith(("transpose(", "jvp(")) and head.endswith(")"):
        head = head[head.index("(") + 1:-1]
    if head in SCOPES:
        return head
    return "recurrence" if head in RECURRENCE else None


def scope(name: str, impl: str = "xla"):
    """``jax.named_scope(name)``, or no scope where ``impl`` is "pallas"."""
    if impl == "pallas":
        return contextlib.nullcontext()
    return jax.named_scope(name)
