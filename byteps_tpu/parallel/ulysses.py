"""Ulysses-style sequence parallelism: all-to-all head/sequence resharding.

Long-context scope beyond reference parity (SURVEY.md §5 notes the
reference has no sequence parallelism); companion to
``byteps_tpu.parallel.ring_attention``.

The DeepSpeed-Ulysses shape: activations arrive sequence-sharded
[B, S/n, H, D]. One ``lax.all_to_all`` over the sequence axis reshards to
head-sharded [B, S, H/n, D] — each device then computes *exact* attention
over the full sequence for its head group (any attention kernel works,
including the Pallas flash kernel) — and a second all-to-all restores
sequence sharding. Communication is two all-to-alls of the activations
(O(B·S·H·D/n) per device) instead of ring attention's n-step K/V rotation;
on an all-to-all-rich ICI fabric this is often the cheaper long-context
schedule when heads divide evenly.

Per-device code: call inside ``jax.shard_map``.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
from jax import lax

from byteps_tpu.jax._compat import axis_size as _axis_size

from byteps_tpu.parallel.ring_attention import full_attention

AttnFn = Callable[..., jax.Array]


def _seq_to_heads(x: jax.Array, axis: str) -> jax.Array:
    # [B, S/n, H, D] -> [B, S, H/n, D]
    return lax.all_to_all(x, axis, split_axis=2, concat_axis=1, tiled=True)


def _heads_to_seq(x: jax.Array, axis: str) -> jax.Array:
    # [B, S, H/n, D] -> [B, S/n, H, D]
    return lax.all_to_all(x, axis, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
    attn_fn: Optional[AttnFn] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on mesh axis ``axis`` via
    head/sequence all-to-all resharding.

    ``q``/``k``/``v``: local blocks [batch, seq_local, heads, head_dim];
    ``heads`` must be divisible by the axis size. ``attn_fn`` replaces the
    inner full-sequence attention (signature: (q, k, v, *, causal, scale));
    defaults to the exact softmax attention, ``full_attention``, which
    picks its form from the per-device shapes it sees here.
    """
    n = _axis_size(axis)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({h}) divisible by the "
            f"'{axis}' axis size ({n}); use ring_attention otherwise")
    inner = attn_fn or full_attention
    if n == 1:
        return inner(q, k, v, causal=causal, scale=scale)

    qh = _seq_to_heads(q, axis)
    kh = _seq_to_heads(k, axis)
    vh = _seq_to_heads(v, axis)
    out = inner(qh, kh, vh, causal=causal, scale=scale)
    return _heads_to_seq(out, axis)


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _ulysses_sharded_impl(q, k, v, mesh, axis, causal, scale, attn_fn):
    from jax.sharding import PartitionSpec as P

    from byteps_tpu.jax._compat import shard_map as _shard_map

    spec = P(None, axis, None, None)
    run = _shard_map(
        lambda ql, kl, vl: ulysses_attention(ql, kl, vl, axis=axis,
                                             causal=causal, scale=scale,
                                             attn_fn=attn_fn),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return run(q, k, v)


def ulysses_attention_sharded(q, k, v, mesh, *, axis: str = "sp",
                              causal: bool = False,
                              scale: Optional[float] = None,
                              attn_fn: Optional[AttnFn] = None):
    """Convenience wrapper: global [B, S, H, D] arrays in, jitted
    shard_map'd Ulysses attention over ``mesh``'s ``axis`` out. The jit
    cache is keyed on (mesh, axis, causal, scale, attn_fn) — loops don't
    recompile (pass a stable ``attn_fn``, not a fresh lambda per call)."""
    return _ulysses_sharded_impl(q, k, v, mesh, axis, causal, scale,
                                 attn_fn)
