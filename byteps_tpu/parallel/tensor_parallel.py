"""Tensor parallelism: Megatron-style column/row-parallel layers.

Beyond-reference scope (SURVEY.md §2.7: BytePS has no TP), added because
the TPU design keeps every mesh axis first-class (§7 "leave the mesh-axis
door open"). The layout is the standard pairing:

    y = f(x @ A) @ B,   A column-sharded, B row-sharded over axis 'tp'
    -> one psum at the pair's output; the activation between A and B
       stays sharded (its heads/hidden slice), never materialised full.

Everything here is *per-device* code for use under ``jax.shard_map`` with
a mesh that has the given axis; the weight tensors passed in are the
LOCAL shards. XLA turns the single ``psum`` per pair into one fused ICI
all-reduce — the whole point of the column-then-row ordering.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.jax._compat import axis_size as _axis_size


def column_parallel(x: jax.Array, w_shard: jax.Array,
                    b_shard: Optional[jax.Array] = None) -> jax.Array:
    """Local half of a column-parallel matmul: returns THIS device's slice
    of the output features. No communication (inputs are replicated)."""
    y = x @ w_shard
    if b_shard is not None:
        y = y + b_shard
    return y


def row_parallel(x_shard: jax.Array, w_shard: jax.Array,
                 axis: str = "tp",
                 bias: Optional[jax.Array] = None) -> jax.Array:
    """Row-parallel matmul closing a column-parallel pair: each device
    contributes a partial product over its input slice; one psum over
    ``axis`` produces the full output on every device. ``bias`` is the
    full (unsharded) bias, added after the reduction."""
    y = lax.psum(x_shard @ w_shard, axis)
    if bias is not None:
        y = y + bias
    return y


def tp_mlp(x: jax.Array, w_in_shard: jax.Array, w_out_shard: jax.Array,
           *, axis: str = "tp",
           activation: Callable[[jax.Array], jax.Array] = jax.nn.gelu,
           b_in_shard: Optional[jax.Array] = None,
           b_out: Optional[jax.Array] = None) -> jax.Array:
    """The canonical TP transformer MLP: column-parallel in-projection,
    activation on the local hidden slice, row-parallel out-projection,
    one all-reduce total."""
    h = activation(column_parallel(x, w_in_shard, b_in_shard))
    return row_parallel(h, w_out_shard, axis, bias=b_out)


def tp_attention(x: jax.Array, wq_shard: jax.Array, wk_shard: jax.Array,
                 wv_shard: jax.Array, wo_shard: jax.Array,
                 *, axis: str = "tp", num_local_heads: int,
                 causal: bool = False,
                 attn_fn: Optional[Callable] = None) -> jax.Array:
    """Head-parallel self-attention: each device owns ``num_local_heads``
    heads end to end (q/k/v column-sharded by head, output row-sharded),
    one psum at the output projection.

    ``x``: [batch, seq, d_model] replicated; w*_shard: [d_model,
    local_heads*head_dim] (wo_shard transposed: [local_heads*head_dim,
    d_model]). ``attn_fn`` defaults to exact softmax attention
    (byteps_tpu.parallel.full_attention, which on a TPU is the Pallas
    flash kernel from s 512 up, causal bf16); pass ``flash_attention``
    to force the kernel.
    """
    from byteps_tpu.parallel.ring_attention import full_attention

    b, s, _ = x.shape
    q = (x @ wq_shard).reshape(b, s, num_local_heads, -1)
    k = (x @ wk_shard).reshape(b, s, num_local_heads, -1)
    v = (x @ wv_shard).reshape(b, s, num_local_heads, -1)
    inner = attn_fn or full_attention
    out = inner(q, k, v, causal=causal)
    out = out.reshape(b, s, -1)
    return row_parallel(out, wo_shard, axis)


def shard_columns(w: jax.Array, axis: str = "tp") -> jax.Array:
    """Per-device code: slice the LAST dim of a replicated weight into
    this device's column shard (convenience for loading unsharded
    checkpoints under shard_map)."""
    n = _axis_size(axis)
    i = lax.axis_index(axis)
    cols = w.shape[-1] // n
    return lax.dynamic_slice_in_dim(w, i * cols, cols, axis=w.ndim - 1)


def shard_rows(w: jax.Array, axis: str = "tp") -> jax.Array:
    """Per-device code: slice the FIRST dim into this device's row shard."""
    n = _axis_size(axis)
    i = lax.axis_index(axis)
    rows = w.shape[0] // n
    return lax.dynamic_slice_in_dim(w, i * rows, rows, axis=0)
