"""Bucketed multi-program overlap for PS-mode training.

The reference's torch plugin starts a gradient's push the moment backward
has produced it (byteps/torch/__init__.py _make_hook; SURVEY.md §7 hard
part #1). JAX has no hooks: a jitted ``value_and_grad`` hands over all
gradients at its end. This module recovers the overlap by **multi-program
stepping**, and holds what is its own: the buckets and one gradient program
per bucket.

* The parameter tree is split into K contiguous, byte-balanced
  **buckets** (model order; processed in reverse = backward order, the
  order autograd hooks would fire in).
* One gradient program per bucket — program b computes
  ``grad(loss, bucket_b)`` only (XLA prunes the rest of the backward
  cone). All K programs are dispatched up front; the device runs them
  back-to-back while the host walks the completed ones. The D2H + PS push
  of bucket b therefore overlaps the backward compute of buckets b+1..K —
  the verbatim overlap contract of the reference's per-parameter hooks,
  with programs playing hooks. The price is recomputation (K forwards +
  progressively deeper partial backwards), which pays off only where the
  device↔host boundary dominates the step.

How a leaf crosses the host boundary is ``ps.py``'s: the tree is bound to
the wire once (``ps.bind``), each finished program's leaves are handed to
the binding as a piece (staged into the pool as they land, enqueued), and
one ``finish`` waits in declaration order and puts each leaf back as it
settles. (One program with the legs pipelined leaf by leaf is
``make_train_step`` in PS mode.)

Semantics match ``training.py``'s PS step exactly — every bucket program's
reduce-and-cast tail and the apply program are that step's own halves:
local chips reduced inside jit (pmean/psum), the DCN leg the C++ core's
(C codecs via ``compression_config``), and with ``average=True`` the
global mean for a homogeneous fleet.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax

import byteps_tpu.jax as bps
from byteps_tpu.jax import ps
from byteps_tpu.jax.compression import Compression
from byteps_tpu.jax.training import ps_apply_step, ps_grad_step


def partition_buckets(sizes: Sequence[int], n_buckets: int) -> List[List[int]]:
    """Split leaf indices into <=n_buckets contiguous groups balanced by
    byte size (greedy: close each bucket once it reaches the ideal
    share). Contiguity preserves model order, so reversed(buckets) is
    backward order — the order the reference's hooks fire in."""
    n_buckets = max(1, min(n_buckets, len(sizes)))
    total = sum(sizes) or 1
    ideal = total / n_buckets
    buckets: List[List[int]] = [[]]
    acc = 0
    for i, s in enumerate(sizes):
        remaining_leaves = len(sizes) - i
        remaining_buckets = n_buckets - len(buckets) + 1
        if (buckets[-1] and acc + s / 2 > ideal * len(buckets)
                and remaining_buckets > 1
                and remaining_leaves >= remaining_buckets):
            buckets.append([])
        buckets[-1].append(i)
        acc += s
    return buckets


def make_bucketed_overlap_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    n_buckets: int = 4,
    average: bool = True,
    wire_dtype: str = "float32",
    compression_config: Optional[str] = None,
    donate: bool = True,
    prefix: str = "bgrad",
):
    """Build ``step(params, opt_state, batch) -> (params, opt_state, loss)``
    with bucketed-overlap PS communication (see module docstring).

    ``loss_fn(params, batch) -> scalar``; ``batch`` leaves carry this
    worker's batch on the leading axis (sharded over the process-local
    mesh). ``n_buckets`` gradient programs give compute/comm overlap at a
    recompute cost. ``wire_dtype="bfloat16"`` casts the wire inside jit
    (half the boundary bytes; cast back before the apply).
    ``compression_config`` is the C-core codec string applied per leaf
    on the DCN leg (e.g. ``"type=onebit;ef=vanilla"``).
    """
    st = bps._st()
    if st.ps_client is None:
        raise RuntimeError(
            "make_bucketed_overlap_step needs PS mode (init with "
            "DMLC_NUM_SERVER>0 / BYTEPS_PS_MODE=ps)")
    if wire_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"wire_dtype must be float32|bfloat16, got {wire_dtype!r}")
    if n_buckets < 1:
        raise ValueError("n_buckets must be >= 1")
    mesh = st.mesh
    cfg = st.config
    axes = tuple(a for a in (cfg.dcn_axis, cfg.ici_axis)
                 if a in mesh.axis_names)
    wire = Compression.bf16 if wire_dtype == "bfloat16" else Compression.none
    apply_jit = ps_apply_step(optimizer, donate)

    def build(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        # what crosses: the gradients, in the wire's dtype
        crossing = jax.eval_shape(
            lambda ls: [wire.compress(l) for l in ls], leaves)
        buckets = partition_buckets(
            [int(np.size(l)) * jnp.dtype(l.dtype).itemsize for l in leaves],
            n_buckets)

        def bucket_grad(idx):
            def value_and_grad(params_, batch):
                ls = jax.tree_util.tree_leaves(params_)

                def loss_of(bucket_vals):
                    full = list(ls)
                    for i, v in zip(idx, bucket_vals):
                        full[i] = v
                    return loss_fn(
                        jax.tree_util.tree_unflatten(treedef, full), batch)

                return jax.value_and_grad(loss_of)([ls[i] for i in idx])

            return ps_grad_step(value_and_grad, mesh, axes, average,
                                wire.compress)

        # Bound in MODEL order: declaration order is PS priority, and
        # front-of-model pulls are needed first by the next forward.
        bound = ps.bind(prefix, crossing, compression=compression_config)
        # backward order: the last bucket's program and leaves first
        return (bound, treedef, [l.dtype for l in leaves],
                [(idx, bucket_grad(idx)) for idx in reversed(buckets)])

    built: list = []  # at the first step: it needs the concrete tree

    def step(params, opt_state, batch):
        if not built:
            built.extend(build(params))
        bound, treedef, dtypes, programs = built
        # Host spans for a jax.profiler capture (names and meaning: jax/ps.py's
        # table; the binding writes bps.ps.d2h / .stage a bucket and
        # bps.ps.wait / .h2d once, on the bridge thread, inside bps.step.ps).
        with jax.profiler.TraceAnnotation(ps.SPAN_STEP_GRAD):
            # Dispatch EVERY program now (async): the device pipelines them
            # back-to-back while the host walks completed buckets.
            outs = [program(params, batch) for _, program in programs]
        with ps.step_ps_span():
            for (idx, _), (_, grads_b) in zip(programs, outs):
                # Blocks only until this program's outputs have landed —
                # later programs keep computing while this bucket crosses
                # D2H and the wire. An error here or in finish() has settled
                # every handle in flight before it leaves the binding.
                bound.push(idx, grads_b, average=average)
            grads = [wire.decompress(g, d)
                     for g, d in zip(bound.finish(), dtypes)]
        with jax.profiler.TraceAnnotation(ps.SPAN_STEP_APPLY):
            params, opt_state = apply_jit(
                params, opt_state,
                jax.tree_util.tree_unflatten(treedef, grads))
        return params, opt_state, outs[0][0]

    return step
