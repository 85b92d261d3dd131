"""Canonical data-parallel training step builder.

The reference's end-user contract (SURVEY.md §3.3): wrap your optimizer,
call ``loss.backward()``; gradients are push_pull'd behind the scenes and
``step()`` applies the synchronized update. The JAX-native equivalent is a
*jitted, shard_map'd step function*: gradients come out of ``value_and_grad``
per-device, ``push_pull`` puts the reduction into the same XLA program
(two mesh levels: reduce-scatter → dcn level → all-gather of one fused
buffer; one level: one all-reduce per gradient leaf), and the optimizer
update runs replicated. XLA overlaps the ICI
collectives with remaining backward compute — the compiler plays the role of
the reference's priority-scheduled background pipeline threads.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import optax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

import byteps_tpu.jax as bps
from byteps_tpu.jax.compression import Compression, Compressor

from byteps_tpu.jax._compat import shard_map as _shard_map


def make_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    mesh: Optional[Mesh] = None,
    *,
    average: bool = True,
    compression: Compressor = Compression.none,
    donate: bool = True,
    ps_prefix: str = "grad",
):
    """Build ``step(params, opt_state, batch) -> (params, opt_state, loss)``.

    ``loss_fn(params, batch) -> scalar``. ``batch`` is a pytree whose leaves
    carry the global batch on their leading axis; it is sharded over the
    (dcn, ici) mesh axes. Params/opt_state are replicated. The returned step
    is jitted with donated params/opt_state (in-place buffer reuse in HBM).

    ``ps_prefix`` names this step's gradient tensors in the PS registry
    (PS mode only). Wire names carry the tree's shape/dtype signature, so
    two step builders may share a prefix; distinct prefixes still help
    trace readability.
    """
    mesh = mesh or bps.mesh()
    cfg = bps._st().config
    axes = tuple(a for a in (cfg.dcn_axis, cfg.ici_axis)
                 if a in mesh.axis_names)

    if cfg.use_ps:
        return _make_ps_train_step(loss_fn, optimizer, mesh, axes, average,
                                   compression, donate, ps_prefix)

    @partial(_shard_map, mesh=mesh,
             in_specs=(P(), P(), P(axes)),
             out_specs=(P(), P(), P()),
             check_vma=False)
    def _step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = bps.push_pull(grads, average=average, compression=compression)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        for ax in axes:
            loss = lax.pmean(loss, ax)
        return params, opt_state, loss

    jit_kwargs = {"donate_argnums": (0, 1)} if donate else {}
    return jax.jit(_step, **jit_kwargs)


def ps_grad_step(value_and_grad, mesh, axes, average, compress):
    """The device half of a PS step before the host boundary, jitted:
    ``value_and_grad(params, batch) -> (loss, grads)`` per chip, the local
    chips reduced inside the program, ``compress`` applied to what leaves
    it. The serial step differentiates the whole tree; a bucket program
    (``bucketed.py``) the leaves of its bucket.

    A gradient of three or more axes leaves the program flat, in row-major
    order (``ps_apply_step`` gives it its shape back). Left in its shape,
    the TPU compiler gives such a result the layout its producer likes (a
    ``[768, 12, 64]`` q/k/v kernel's gradient comes out with axis 0
    minor-most), the runtime lands it on the host in that layout, and the
    wire is row-major: the host would pay a transposing copy of every such
    leaf every step (85 of the 498 MB of a GPT-2 tree) where the device
    pays a relayout at HBM speed. Flat and not pinned to a row-major
    layout in its own shape, because that one is tiled over the last two
    axes: 12 x 64 pads to 16 x 128, 142 MB of HBM for the same 36 leaves
    (PERF.md, PR 49). Vectors and matrices land row-major as they are."""

    @jax.jit
    @partial(_shard_map, mesh=mesh, in_specs=(P(), P(axes)),
             out_specs=(P(), P()), check_vma=False)
    def grad_step(params, batch):
        loss, grads = value_and_grad(params, batch)
        reduce = lax.pmean if average else lax.psum
        for ax in axes:
            grads = jax.tree_util.tree_map(
                lambda g, a=ax: reduce(g, a), grads)
            loss = lax.pmean(loss, ax)
        grads = jax.tree_util.tree_map(compress, grads)
        return loss, jax.tree_util.tree_map(
            lambda g: g.reshape(-1) if g.ndim > 2 else g, grads)

    return grad_step


def ps_apply_step(optimizer, donate):
    """The device half after it: the optimizer on the summed gradients,
    each in its parameter's shape again."""

    def apply_step(params, opt_state, grads):
        grads = jax.tree_util.tree_map(lambda g, p: g.reshape(p.shape),
                                       grads, params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    return jax.jit(apply_step, donate_argnums=(0, 1) if donate else ())


def _make_ps_train_step(loss_fn, optimizer, mesh, axes, average, compression,
                        donate, prefix="grad"):
    """PS-mode step: local-chip level inside jit, cross-host DCN level
    through the C++ KV client to the CPU parameter servers (SURVEY.md
    §3.3's two-level pipeline with XLA playing NCCL and the core playing
    ps-lite).

    In PS mode the mesh is process-local (one BytePS worker per controller
    process), so the in-jit reduction covers exactly this host's chips.
    Semantics match the collective path: average=True gives the global mean
    (local pmean, then PS average over equal-sized workers); average=False
    gives the global sum (local psum, then PS sum). Wire compression is
    applied inside jit before the host transfer (XLA fuses the cast) and
    undone after the pull.
    """
    from byteps_tpu.jax import ps

    if compression.name in ("int8_quant", "int8_quant_dcn"):
        # int8_quant replaces the *collective transport* (all-to-all of
        # int8 chunks + scales); in PS mode its compress fn is an identity,
        # so the DCN leg would silently ship uncompressed f32. The PS wire
        # has its own codec framework — point the user there.
        raise ValueError(
            f"Compression {compression.name!r} (int8 quantized transport) "
            "only applies to collective mode. In PS mode use the C-core "
            "codec instead: declare tensors with a compressor config "
            "string (e.g. BYTEPS_COMPRESSOR=onebit or type=dithering;k=4), "
            "or use Compression.bf16/fp16 for an in-jit wire cast.")

    grad_step = ps_grad_step(jax.value_and_grad(loss_fn), mesh, axes, average,
                             compression.compress)
    apply_jit = ps_apply_step(optimizer, donate)

    def step(params, opt_state, batch):
        # Host spans for a jax.profiler capture (names: jax/ps.py's table);
        # each is a no-op context while no capture runs.
        with jax.profiler.TraceAnnotation(ps.SPAN_STEP_GRAD):
            loss, grads = grad_step(params, batch)
        dtypes = jax.tree_util.tree_map(lambda p: p.dtype, params)
        with ps.step_ps_span():
            grads = ps.ps_push_pull(grads, average=average, prefix=prefix)
            grads = jax.tree_util.tree_map(
                lambda g, d: compression.decompress(g, d), grads, dtypes)
        with jax.profiler.TraceAnnotation(ps.SPAN_STEP_APPLY):
            params, opt_state = apply_jit(params, opt_state, grads)
        return params, opt_state, loss

    return step


def make_async_train_step(
    loss_fn: Callable,
    optimizer: optax.GradientTransformation,
    params,
    *,
    prefix: str = "aparam",
):
    """Asynchronous PS training (reference: BYTEPS_ENABLE_ASYNC,
    server.cc async path): the SERVER holds the parameters as a
    server-resident accumulator; each worker, at its own pace and with no
    per-round barrier, computes a local update and pushes the DELTA, then
    pulls whatever the parameters currently are — stale gradients by
    design.

    ``params`` is the initial pytree; call on every worker with identical
    values BEFORE training (it seeds the server copy via ps_broadcast from
    rank 0). Returns ``step(params, opt_state, batch) ->
    (params, opt_state, loss)`` where the returned params are the freshly
    pulled server state.
    """
    from byteps_tpu.jax import ps

    # Seed (raises unless PS mode is active, DMLC_NUM_SERVER>0): rank 0's
    # initial params become the server-resident copy —
    # CMD_BCAST_PUSH initialises the async accumulator for THE SAME wire
    # keys the step pushes deltas to, and everyone starts from the same
    # values.
    params = ps.ps_broadcast(params, root_rank=0, prefix=prefix)
    leaves0, treedef = jax.tree_util.tree_flatten(params)
    # Bound from the PARAMETERS' leaves: the binding the broadcast above
    # made (same prefix and shape signature, so nothing is declared here).
    # Keys of the updates' own would be fresh, never-initialised server
    # tensors — the first delta would silently BECOME the parameters
    # instead of updating them.
    wire = ps.bind(prefix, leaves0)

    @jax.jit
    def local_update(p, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        updates, opt_state = optimizer.update(grads, opt_state, p)
        return updates, opt_state, loss

    def step(params, opt_state, batch):
        updates, opt_state, loss = local_update(params, opt_state, batch)
        fresh = wire.push_pull(jax.tree_util.tree_leaves(updates),
                               average=False, async_mode=True)
        return (jax.tree_util.tree_unflatten(treedef, fresh), opt_state,
                loss)

    return params, step


def replicate(tree, mesh: Optional[Mesh] = None):
    """Place a host pytree replicated on every device of the mesh."""
    mesh = mesh or bps.mesh()
    sharding = jax.sharding.NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), tree)


def shard_batch(batch, mesh: Optional[Mesh] = None):
    """Shard a host batch over the data-parallel mesh axes (leading dim).

    Single-controller: ``batch`` carries the GLOBAL batch and is laid out
    over the mesh. Multi-controller (``jax.distributed`` across hosts —
    the collective-mode analogue of the reference's one-process-per-GPU
    fleets): ``batch`` carries THIS PROCESS's shard (the Horovod
    contract — shard your input by ``rank()``), and the shards are
    assembled into one global array spanning all hosts.
    """
    mesh = mesh or bps.mesh()
    cfg = bps._st().config
    axes = tuple(a for a in (cfg.dcn_axis, cfg.ici_axis)
                 if a in mesh.axis_names)
    sharding = jax.sharding.NamedSharding(mesh, P(axes))
    if jax.process_count() > 1:
        import numpy as np
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(
                sharding, np.asarray(x)), batch)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sharding), batch)
