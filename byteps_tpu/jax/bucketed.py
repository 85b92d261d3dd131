"""Bucketed multi-program overlap for PS-mode training — no host callbacks.

SURVEY.md §7 hard part #1 names three designs for recovering the
reference's hook-style push streaming (byteps/torch/__init__.py
_make_hook) in JAX: custom_vjp taps (``overlap.py``, host callbacks
inside the jitted backward), donated double-buffers, or **multi-program
stepping**. This module is the third:

* The parameter tree is split into K contiguous, byte-balanced
  **buckets** (model order; processed in reverse = backward order, the
  order autograd hooks would fire in).
* ``multi_program=True`` compiles one gradient program per bucket —
  program b computes ``grad(loss, bucket_b)`` only (XLA prunes the rest
  of the backward cone). All K programs are dispatched up front; the
  device runs them back-to-back while the host walks the completed ones.
  The D2H + PS push of bucket b therefore overlaps the backward compute
  of buckets b+1..K — the verbatim overlap contract of the reference's
  per-parameter hooks, with programs playing hooks. The price is
  recomputation (K forwards + progressively deeper partial backwards),
  which pays off only where the device↔host boundary dominates the
  step; it needs no host callbacks.
* ``multi_program=False`` compiles ONE gradient program (no recompute)
  and recovers the boundary-leg pipeline only: the D2H of bucket b
  overlaps the network round of buckets < b and the H2D of buckets
  already pulled. On boundary-dominated hosts this captures most of the
  win at zero compute overhead.

Either way the three host-boundary legs — D2H, DCN push/pull, H2D — run
as a bucket pipeline instead of tree-serial phases: steady-state step
time approaches max(leg) + compute instead of sum(legs) + compute.
Completed buckets start their (async-dispatch) H2D upload immediately,
while later buckets are still crossing D2H or the wire.

Semantics match ``training.py``'s PS step exactly: local chips are
reduced inside jit over the process-local mesh (pmean/psum), the C++
core handles the DCN leg (partitioning, priority-credit scheduling,
C codecs via ``compression_config``, CPU summation), and with
``average=True`` the result is the global mean for a homogeneous fleet.
"""

from __future__ import annotations

import os
from functools import partial
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

import byteps_tpu.jax as bps
from byteps_tpu.jax._compat import shard_map as _shard_map
from byteps_tpu.jax.ps import _wait_all, _writable


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


class _BucketPipeline:
    """Host-side leg pipeline over one step's buckets.

    Tracks per-bucket staged host buffers + C-core handles; uploads a
    bucket (async device_put) the moment its pulls complete, so H2D of
    bucket j rides under the D2H/network of buckets processed later.
    All error paths settle EVERY outstanding handle before raising —
    bailing early would free staging buffers that live-server partitions
    still write into (the Wait/Poll settle invariant, kept one layer up).
    """

    def __init__(self, client):
        self.client = client
        # bucket_idx -> list of (handle, staged_array, leaf_idx)
        self.pending: dict = {}
        self.uploaded: dict = {}

    def push_bucket(self, b: int, tids, host_arrays, leaf_idx, average):
        # Register the bucket BEFORE the first enqueue: if push_pull
        # raises mid-bucket, the already-staged handles are visible to
        # settle_all() on the step's error path.
        staged: list = []
        self.pending[b] = staged
        for tid, arr, li in zip(tids, host_arrays, leaf_idx):
            arr = _writable(np.asarray(arr))
            h = self.client.push_pull(tid, arr.reshape(-1),
                                      average=average)
            staged.append((h, arr, li))

    def sweep(self):
        """Non-blocking: upload any bucket whose pulls have all landed.
        poll() raises on a failed handle — the caller's error path
        settles everything else via settle_all()."""
        done = [b for b, staged in self.pending.items()
                if all(self.client.poll(h) for h, _, _ in staged)]
        for b in done:
            self._upload(b)

    def _upload(self, b: int):
        staged = self.pending.pop(b)
        # ONE batched async device_put per bucket: dispatch returns
        # immediately, the runtime overlaps the transfer with whatever
        # the device/host do next.
        devs = jax.device_put([arr for _, arr, _ in staged])
        for d, (_, _, li) in zip(devs, staged):
            self.uploaded[li] = d

    def _settle_pending(self):
        """Wait out EVERY pending handle (never bail early — a freed
        staging buffer with a live-server partition in flight is a
        use-after-free); return the first error, leaving ``pending``
        intact for the caller to consume or clear."""
        err = None
        for staged in self.pending.values():
            try:
                _wait_all(self.client, staged)
            except Exception as e:  # noqa: BLE001 — settle every bucket
                if err is None:
                    err = e
        return err

    def finish(self) -> dict:
        """Wait out every remaining bucket, upload, and return
        {leaf_idx: device_array}."""
        err = self._settle_pending()
        if err is not None:
            self.pending.clear()
            self.uploaded = {}
            raise err
        for b in sorted(self.pending):
            self._upload(b)
        self.pending.clear()
        out, self.uploaded = self.uploaded, {}
        return out

    def settle_all(self) -> None:
        """Quiet settle for error paths: waits everything out, swallows
        settle-time errors (the caller re-raises the original)."""
        self._settle_pending()
        self.pending.clear()
        self.uploaded = {}


def make_bucketed_overlap_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    *,
    n_buckets: Optional[int] = None,
    multi_program: Optional[bool] = None,
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
    mesh). ``n_buckets`` defaults to ``BYTEPS_OVERLAP_BUCKETS`` (4).
    ``multi_program`` defaults to ``BYTEPS_BUCKET_PROGRAMS`` ∈
    {``multi``, ``single``} (multi): per-bucket gradient programs give
    true compute/comm overlap at a recompute cost; ``single`` gives
    boundary-leg pipelining only. ``wire_dtype="bfloat16"`` casts the
    wire inside jit (half the boundary bytes; the apply casts back).
    ``compression_config`` is the C-core codec string applied per leaf
    on the DCN leg (e.g. ``"type=onebit;ef=vanilla"``).
    """
    st = bps._st()
    client = st.ps_client
    if client is None:
        raise RuntimeError(
            "make_bucketed_overlap_step needs PS mode (init with "
            "DMLC_NUM_SERVER>0 / BYTEPS_PS_MODE=ps)")
    if wire_dtype not in ("float32", "bfloat16"):
        raise ValueError(
            f"wire_dtype must be float32|bfloat16, got {wire_dtype!r}")
    if n_buckets is None:
        n_buckets = int(os.environ.get("BYTEPS_OVERLAP_BUCKETS", "4"))
    if n_buckets < 1:
        raise ValueError("n_buckets must be >= 1")
    if multi_program is None:
        multi_program = os.environ.get(
            "BYTEPS_BUCKET_PROGRAMS", "multi").lower() != "single"
    mesh = st.mesh
    cfg = st.config
    axes = tuple(a for a in (cfg.dcn_axis, cfg.ici_axis)
                 if a in mesh.axis_names)
    wire = jnp.bfloat16 if wire_dtype == "bfloat16" else None

    # Filled lazily at the first step (needs the concrete param tree).
    built: dict = {}

    def _build(params):
        leaves, treedef = jax.tree_util.tree_flatten(params)
        sizes = [int(np.size(l)) * jnp.dtype(l.dtype).itemsize
                 for l in leaves]
        buckets = partition_buckets(sizes, n_buckets)
        # Declare in MODEL order: declaration order is PS priority, and
        # front-of-model pulls are needed first by the next forward.
        tids = [client.declare(
                    f"{prefix}_{i}", int(np.size(l)),
                    wire_dtype if wire is not None
                    else jnp.dtype(l.dtype).name,
                    compression=compression_config)
                for i, l in enumerate(leaves)]
        shapes = [jnp.shape(l) for l in leaves]
        dtypes = [jnp.dtype(l.dtype) for l in leaves]

        def cast_wire(g):
            return g.astype(wire) if wire is not None else g

        def merged_loss(bucket_vals, other_vals, batch, idx, other_idx):
            full: List = [None] * len(leaves)
            for v, i in zip(bucket_vals, idx):
                full[i] = v
            for v, i in zip(other_vals, other_idx):
                full[i] = v
            return loss_fn(jax.tree_util.tree_unflatten(treedef, full),
                           batch)

        def reduce_local(loss, grads):
            red = lax.pmean if average else lax.psum
            for ax in axes:
                grads = jax.tree_util.tree_map(
                    lambda g, a=ax: red(g, a), grads)
                loss = lax.pmean(loss, ax)
            return loss, jax.tree_util.tree_map(cast_wire, grads)

        if multi_program:
            programs = []
            for idx in buckets:
                other_idx = [i for i in range(len(leaves))
                             if i not in set(idx)]

                def grad_b(params_, batch, idx=tuple(idx),
                           other_idx=tuple(other_idx)):
                    ls = jax.tree_util.tree_flatten(params_)[0]
                    bucket_vals = [ls[i] for i in idx]
                    other_vals = [ls[i] for i in other_idx]
                    loss, g = jax.value_and_grad(merged_loss)(
                        bucket_vals, other_vals, batch, idx, other_idx)
                    return reduce_local(loss, g)

                programs.append(jax.jit(partial(
                    _shard_map, mesh=mesh, in_specs=(P(), P(axes)),
                    out_specs=(P(), P()), check_vma=False)(grad_b)))
            built["programs"] = programs
        else:
            @jax.jit
            @partial(_shard_map, mesh=mesh, in_specs=(P(), P(axes)),
                     out_specs=(P(), P()), check_vma=False)
            def grad_all(params_, batch):
                loss, grads = jax.value_and_grad(loss_fn)(params_, batch)
                return reduce_local(loss, grads)

            built["grad_all"] = grad_all

        def apply_fn(params_, opt_state, flat_grads):
            gl = [g.reshape(s).astype(d)
                  for g, s, d in zip(flat_grads, shapes, dtypes)]
            grads = jax.tree_util.tree_unflatten(treedef, gl)
            updates, opt_state = optimizer.update(grads, opt_state,
                                                  params_)
            return optax.apply_updates(params_, updates), opt_state

        # The outputs are exactly (params, opt_state): donate those when
        # the caller allows, else the per-step gradient buffers (same
        # shapes as the params). Donating all three leaves the gradient
        # buffers with no output to alias — the TPU compiler then warns
        # "Some donated buffers were not usable".
        built["apply"] = jax.jit(
            apply_fn, donate_argnums=(0, 1) if donate else (2,))
        built["buckets"] = buckets
        built["tids"] = tids
        built["treedef"] = treedef
        built["n_leaves"] = len(leaves)

    def step(params, opt_state, batch):
        if not built:
            _build(params)
        buckets = built["buckets"]
        tids = built["tids"]
        order = list(reversed(range(len(buckets))))  # backward order
        pipe = _BucketPipeline(client)
        try:
            if multi_program:
                # Dispatch EVERY program now (async): the device
                # pipelines them back-to-back while the host walks
                # completed buckets.
                outs = [built["programs"][b](params, batch) for b in order]
                loss = outs[0][0]
                for (_, grads_b), b in zip(outs, order):
                    # Blocks only until program b's outputs are ready —
                    # later programs keep computing while this bucket
                    # crosses D2H and the wire.
                    host = jax.device_get(list(grads_b))
                    pipe.push_bucket(b, [tids[i] for i in buckets[b]],
                                     host, buckets[b], average)
                    pipe.sweep()
            else:
                loss, grads = built["grad_all"](params, batch)
                flat = jax.tree_util.tree_flatten(grads)[0]
                for b in order:
                    host = jax.device_get([flat[i] for i in buckets[b]])
                    pipe.push_bucket(b, [tids[i] for i in buckets[b]],
                                     host, buckets[b], average)
                    pipe.sweep()
            by_leaf = pipe.finish()
        except Exception:
            # Settle-before-raise, one level up from every fault site
            # (enqueue, poll, device transfer): no staging buffer is
            # freed while a live-server partition can still write it.
            pipe.settle_all()
            raise
        flat_grads = [by_leaf[i] for i in range(built["n_leaves"])]
        params, opt_state = built["apply"](params, opt_state, flat_grads)
        return params, opt_state, loss

    return step
