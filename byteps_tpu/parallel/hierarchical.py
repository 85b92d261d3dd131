"""Hierarchical two-level all-reduce — the heart of the framework.

Capability parity with the reference's core pipeline (SURVEY.md §3.3,
byteps/common/core_loops.cc): NCCL reduce-scatter intra-node → push/pull to
CPU parameter servers inter-node → NCCL broadcast/all-gather back. The
TPU-native mapping:

    REDUCE (NCCL reduce-scatter)  →  lax.psum_scatter over the ``ici`` axis
    PUSH/PULL (ps-lite over TCP)  →  ``dcn_reduce_fn``: either
                                     lax.psum over the ``dcn`` axis
                                     (XLA DCN collective, collective mode)
                                     or a host callback into the C++ KV
                                     client → CPU PS (PS mode)
    BROADCAST (NCCL all-gather)   →  lax.all_gather over the ``ici`` axis

That three-stage shape exists to put 1/N-sized shards on the slow fabric,
so it runs only on a mesh that has both levels. Where one level alone has
more than one participant (a single slice: {dcn 1, ici n}) there is no slow
fabric to shard for, and each array is reduced by one ``lax.psum`` in its
own shape: the TPU compiler lowers either half of a scatter / gather pair
to a full-size all-reduce, so the pair would pay twice for one reduction.
The choice is made at trace time from the mesh's axis sizes.

Every function here is *per-device* code: call it inside ``jax.shard_map``
over a mesh with the named axes. Shapes are static; on the two-level path
padding is applied so reduce-scatter tiles evenly — both required for XLA
to schedule the collectives on ICI without host round-trips.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.jax._compat import axis_size as _compat_axis_size

ReduceFn = Callable[[jax.Array], jax.Array]


def _axis_size(axis: Optional[str]) -> int:
    return _compat_axis_size(axis) if axis else 1


def _levels(ici_axis: Optional[str], dcn_axis: Optional[str]):
    """The axes that have more than one participant (else None): static at
    trace time, so the shape of the reduction is decided per compile."""
    ici = ici_axis if ici_axis and _axis_size(ici_axis) > 1 else None
    dcn = dcn_axis if dcn_axis and _axis_size(dcn_axis) > 1 else None
    return ici, dcn


def hierarchical_all_reduce(
    x: jax.Array,
    *,
    ici_axis: Optional[str] = "ici",
    dcn_axis: Optional[str] = "dcn",
    average: bool = True,
    dcn_reduce_fn: Optional[ReduceFn] = None,
) -> jax.Array:
    """All-reduce of one array (per-device code under shard_map).

    Two levels: stage 1 reduce-scatters over the fast ``ici`` axis so each
    chip owns 1/ici_size of the gradient; stage 2 reduces those shards over
    the slow ``dcn`` axis (or hands them to ``dcn_reduce_fn`` — the PS
    hook); stage 3 all-gathers the result back over ``ici``. With 1/N-sized
    shards on the slow fabric this is bandwidth-optimal, exactly the
    reference's rationale (docs/rationale.md) transplanted to ICI/DCN.

    One level (at most one of ``ici``, ``dcn`` has more than one
    participant): one ``lax.psum`` over that axis on ``x`` as it is — no
    flattening, pad, scatter or gather. ``dcn_reduce_fn``, when given and
    the level is ``dcn``, receives the flat array in its place.
    """
    ici, dcn = _levels(ici_axis, dcn_axis)
    denom = _axis_size(ici) * _axis_size(dcn)
    orig_shape, orig_dtype = x.shape, x.dtype

    if ici is None or dcn is None:
        axis = ici or dcn
        if axis is None:
            return x
        if dcn and dcn_reduce_fn:
            x = dcn_reduce_fn(x.reshape(-1)).reshape(orig_shape)
        else:
            x = lax.psum(x, axis)
        if average:
            x = x / denom
        return x.astype(orig_dtype)

    flat = x.reshape(-1)
    n = flat.shape[0]
    ici_size = _axis_size(ici)
    pad = (-n) % ici_size
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])

    shard = lax.psum_scatter(flat, ici, scatter_dimension=0, tiled=True)
    shard = dcn_reduce_fn(shard) if dcn_reduce_fn else lax.psum(shard, dcn)
    if average:
        shard = shard / denom
    out = lax.all_gather(shard, ici, axis=0, tiled=True)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape).astype(orig_dtype)


def tree_all_reduce(
    tree,
    *,
    ici_axis: Optional[str] = "ici",
    dcn_axis: Optional[str] = "dcn",
    average: bool = True,
    dcn_reduce_fn: Optional[ReduceFn] = None,
    fuse: bool = True,
) -> "jax.tree_util.PyTreeDef":
    """All-reduce a pytree of arrays (per-device code under shard_map).

    On a two-level mesh ``fuse=True`` flattens all leaves into one
    contiguous buffer in their widest dtype first (reference analogue:
    tensor fusion, and the reason BytePS partitions at ~4 MB — big
    transfers saturate the fabric; SURVEY.md §6 "saturates 100 Gbps with
    ≥4 MB partitions"): one reduce-scatter → slow level → all-gather for
    the whole tree, and one call of ``dcn_reduce_fn``.

    On one level there is nothing to fuse: every leaf takes one all-reduce
    in its own shape, summed in that same widest dtype and cast back, and
    XLA's all-reduce combiner does the grouping — no tree-sized buffer is
    built, copied or sliced. (A ``dcn_reduce_fn`` on a dcn-only mesh still
    gets the fused buffer: one call of the hook per tree.)
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    # Axis sizes are static at trace time: when neither level has >1
    # participant the all-reduce is the identity, and the fused
    # concat/slice round-trip would be pure single-chip HBM tax
    # (~200 MB of extra reads+writes per step on ResNet-50).
    if _axis_size(ici_axis) * _axis_size(dcn_axis) == 1:
        return tree
    reduce = partial(
        hierarchical_all_reduce, ici_axis=ici_axis, dcn_axis=dcn_axis,
        average=average, dcn_reduce_fn=dcn_reduce_fn)
    if not fuse:
        return jax.tree_util.tree_unflatten(
            treedef, [reduce(g) for g in leaves])

    acc_dtype = jnp.result_type(*[l.dtype for l in leaves])
    ici, dcn = _levels(ici_axis, dcn_axis)
    if not (dcn and (ici or dcn_reduce_fn)):
        # No slow level to shard or batch for: one psum per leaf, in the
        # fused path's precision.
        out = [reduce(l.astype(acc_dtype)).astype(l.dtype) for l in leaves]
        return jax.tree_util.tree_unflatten(treedef, out)

    # Fused path: one flat buffer in the widest participating dtype.
    sizes = [l.size for l in leaves]
    flat = reduce(
        jnp.concatenate([l.reshape(-1).astype(acc_dtype) for l in leaves]))
    out, off = [], 0
    for leaf, sz in zip(leaves, sizes):
        out.append(flat[off:off + sz].reshape(leaf.shape).astype(leaf.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)


def hierarchical_broadcast(
    x: jax.Array,
    *,
    root: int = 0,
    ici_axis: Optional[str] = "ici",
    dcn_axis: Optional[str] = "dcn",
) -> jax.Array:
    """Broadcast ``x`` from the device with linearised index ``root``.

    Reference analogue: ``broadcast_parameters`` (SURVEY.md §3.4) — root's
    values pushed, everyone pulls the same buffer. Implemented as a masked
    psum (zero everywhere but root), which XLA lowers to an efficient
    broadcast over ICI+DCN.
    """
    ici = ici_axis if ici_axis and _axis_size(ici_axis) > 1 else None
    dcn = dcn_axis if dcn_axis and _axis_size(dcn_axis) > 1 else None
    idx = jnp.int32(0)
    scale = 1
    if ici is not None:
        idx = idx + lax.axis_index(ici)
        scale = _axis_size(ici)
    if dcn is not None:
        idx = idx + lax.axis_index(dcn) * scale
    mask = (idx == root).astype(x.dtype)
    y = x * mask
    if ici is not None:
        y = lax.psum(y, ici)
    if dcn is not None:
        y = lax.psum(y, dcn)
    return y


def tree_broadcast(tree, *, root: int = 0,
                   ici_axis: Optional[str] = "ici",
                   dcn_axis: Optional[str] = "dcn"):
    """Broadcast a pytree from ``root`` (per-device code under shard_map)."""
    return jax.tree_util.tree_map(
        lambda x: hierarchical_broadcast(
            x, root=root, ici_axis=ici_axis, dcn_axis=dcn_axis),
        tree)


def _blockwise_quantize(x: jax.Array, block: int):
    """int8-quantize with one f32 scale per ``block`` values (x is padded
    to a block multiple by the caller). Returns (q[int8], scales[f32])."""
    b = x.reshape(-1, block).astype(jnp.float32)
    scale = jnp.max(jnp.abs(b), axis=1, keepdims=True) / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(b / safe), -127, 127).astype(jnp.int8)
    return q, scale


def _blockwise_dequantize(q: jax.Array, scale: jax.Array) -> jax.Array:
    return (q.astype(jnp.float32) * scale).reshape(-1)


def _quantized_reduce_scatter(flat: jax.Array, axis: str, block: int
                              ) -> jax.Array:
    """int8 reduce-scatter over ``axis``: quantize per destination chunk,
    all-to-all the int8 chunks + per-block f32 scales, sum dequantized
    locally. ``flat`` length must be divisible by (axis_size * block).
    Returns this device's 1/k shard of the sum in f32."""
    k = _axis_size(axis)
    chunk = flat.shape[0] // k
    q, scale = _blockwise_quantize(flat, block)           # [nb, block]
    q = q.reshape(k, chunk // block, block)
    scale = scale.reshape(k, chunk // block, 1)
    q_recv = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    s_recv = lax.all_to_all(scale, axis, split_axis=0, concat_axis=0,
                            tiled=False)
    return jnp.sum(q_recv.astype(jnp.float32) * s_recv, axis=0).reshape(-1)


def _quantized_all_gather(shard: jax.Array, axis: str, block: int
                          ) -> jax.Array:
    """int8 all-gather over ``axis``: each device ships its quantized
    shard + scales; everyone dequantizes the concatenation."""
    q, s = _blockwise_quantize(shard, block)
    q_all = lax.all_gather(q, axis, axis=0, tiled=True)
    s_all = lax.all_gather(s, axis, axis=0, tiled=True)
    return _blockwise_dequantize(q_all, s_all)


def quantized_all_reduce(
    x: jax.Array,
    *,
    ici_axis: Optional[str] = "ici",
    dcn_axis: Optional[str] = "dcn",
    average: bool = True,
    block: int = 256,
    quantize_dcn: bool = False,
) -> jax.Array:
    """Hierarchical all-reduce with int8 blockwise-quantized transport
    (EQuARX-style, PAPERS.md: arXiv 2506.17615): ~4x the effective
    bandwidth of f32 (2x bf16) at ~1e-2 relative error per stage.

    Per-device code under shard_map. Each quantized level runs the same
    scheme: reduce-scatter becomes an all-to-all of int8 chunks +
    per-block f32 scales with local f32 summation, and the return
    all-gather ships int8 too.

    ``quantize_dcn=False`` (default) keeps the cross-slice stage exact
    (f32 psum) — double quantization compounds error, and in PS mode the
    DCN bytes are the C-core codec layer's job. ``quantize_dcn=True``
    applies the same int8 scheme to the dcn axis: in pure collective
    mode the DCN is the *slow* fabric, so that is where the 4x matters
    most; each shard crosses DCN as int8 both ways. Pair with error
    feedback at the optimizer level if the noise matters.
    """
    ici = ici_axis if ici_axis and _axis_size(ici_axis) > 1 else None
    dcn = dcn_axis if dcn_axis and _axis_size(dcn_axis) > 1 else None
    denom = _axis_size(ici) * _axis_size(dcn)

    orig_shape, orig_dtype = x.shape, x.dtype
    flat = x.reshape(-1)
    n = flat.shape[0]

    if ici is None and dcn is None:
        return x
    if ici is None:
        # Single-chip slices: the dcn axis is the only level. With
        # quantize_dcn it becomes the (sole) quantized level — fall
        # through to the generic stages with dcn playing ici's role.
        if quantize_dcn:
            ici, dcn = dcn, None
        else:
            flat = lax.psum(flat, dcn)
            if average and denom > 1:
                flat = flat / denom
            return flat.reshape(orig_shape).astype(orig_dtype)

    k = _axis_size(ici)
    kd = _axis_size(dcn) if dcn else 1
    # Pad so the ici shard also tiles (dcn_size * block) when the dcn
    # level is quantized too.
    pad = (-n) % (k * kd * block if (dcn and quantize_dcn) else k * block)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])

    # Stage 1: int8 reduce-scatter over the fast axis.
    shard = _quantized_reduce_scatter(flat, ici, block)

    # Stage 2: cross-slice reduction — exact psum, or the same int8
    # scheme when the slow fabric's bytes dominate.
    if dcn is not None:
        if quantize_dcn:
            dshard = _quantized_reduce_scatter(shard, dcn, block)
            if average:
                dshard = dshard / denom
            shard = _quantized_all_gather(dshard, dcn, block)
        else:
            shard = lax.psum(shard, dcn)
            if average:
                shard = shard / denom
    elif average and denom > 1:
        shard = shard / denom

    # Stage 3: int8 all-gather back over the fast axis.
    out = _quantized_all_gather(shard, ici, block)
    if pad:
        out = out[:n]
    return out.reshape(orig_shape).astype(orig_dtype)


def tree_quantized_all_reduce(
    tree,
    *,
    ici_axis: Optional[str] = "ici",
    dcn_axis: Optional[str] = "dcn",
    average: bool = True,
    block: int = 256,
    quantize_dcn: bool = False,
):
    """Fused pytree variant of quantized_all_reduce: one flat f32 buffer,
    one quantized collective pair (tensor fusion, as tree_all_reduce)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree
    if _axis_size(ici_axis) * _axis_size(dcn_axis) == 1:
        return tree  # identity on a 1x1 mesh — skip the quantize round-trip
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate(
        [l.reshape(-1).astype(jnp.float32) for l in leaves])
    flat = quantized_all_reduce(flat, ici_axis=ici_axis, dcn_axis=dcn_axis,
                                average=average, block=block,
                                quantize_dcn=quantize_dcn)
    out, off = [], 0
    for leaf, sz in zip(leaves, sizes):
        out.append(flat[off:off + sz].reshape(leaf.shape)
                   .astype(leaf.dtype))
        off += sz
    return jax.tree_util.tree_unflatten(treedef, out)
