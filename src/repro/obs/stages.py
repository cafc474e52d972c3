"""Named stages of the training steps.

Each stage is a function that calls its argument under
``jax.named_scope(<stage>)``:

===========  ======================================================
stage        what runs under it
===========  ======================================================
sample       the device sampler (and the seed mask it draws with)
gather       feature-row gathers (a batch's source rows, a block's
             destination rows)
normalize    the in-step GCN normalisation of the unpatched path
aggregate    every SpMM / block-SpMM / FusedMM of a model layer
attention    a GAT layer's attention: the node scores, the slot
             logits, LeakyReLU and the edge softmax, their backward
             and the SDDMM of the weights' gradient
dense        the layers' products, biases and activations
loss         the cross-entropy (and its label gather)
grad_sync    every collective of a step: the non-finite vote, the
             gradient sync, the loss and overflow reductions
optimizer    the non-finite guard, the AdamW update and the selects
===========  ======================================================

Two readers see a stage. XProf and TensorBoard group ops by the name
scope (``jit(step)/jvp(aggregate)/...``, ``transpose(jvp(aggregate))``).
And because a stage is a function, every op it creates carries the frame
(``repro/obs/stages.py``, ``<stage>``) in its creating Python stack, which
the optimized HLO keeps as op metadata; backward ops keep their forward
op's stack. A trace reduction joins device ops to stages by that frame.

Stages do not nest, and each call sits close to the ops it wraps: JAX keeps
only the innermost ``jax_traceback_in_locations_limit`` (10) user frames of
an op's stack. The stages change only metadata, never the compiled program.
One limit: a jnp function that JAX jits on its own (``sort``,
``searchsorted``, ...) keeps the ops of its first trace in the process, so
if that trace ran outside a stage (an eager call, another program), its ops
carry that first stack in a later step too.
"""
from __future__ import annotations

import jax

__all__ = ["STAGES", "sample", "gather", "normalize", "aggregate",
           "attention", "dense", "loss", "grad_sync", "optimizer"]

STAGES = ("sample", "gather", "normalize", "aggregate", "attention", "dense",
          "loss", "grad_sync", "optimizer")


def sample(fn, *args, **kwargs):
    with jax.named_scope("sample"):
        return fn(*args, **kwargs)


def gather(fn, *args, **kwargs):
    with jax.named_scope("gather"):
        return fn(*args, **kwargs)


def normalize(fn, *args, **kwargs):
    with jax.named_scope("normalize"):
        return fn(*args, **kwargs)


def aggregate(fn, *args, **kwargs):
    with jax.named_scope("aggregate"):
        return fn(*args, **kwargs)


def attention(fn, *args, **kwargs):
    with jax.named_scope("attention"):
        return fn(*args, **kwargs)


def dense(fn, *args, **kwargs):
    with jax.named_scope("dense"):
        return fn(*args, **kwargs)


def loss(fn, *args, **kwargs):
    with jax.named_scope("loss"):
        return fn(*args, **kwargs)


def grad_sync(fn, *args, **kwargs):
    with jax.named_scope("grad_sync"):
        return fn(*args, **kwargs)


def optimizer(fn, *args, **kwargs):
    with jax.named_scope("optimizer"):
        return fn(*args, **kwargs)
