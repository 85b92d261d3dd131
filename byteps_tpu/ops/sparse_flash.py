"""Pallas TPU kernels for the attention of one block of queries over a
token-level selection of keys (``byteps_tpu.parallel.sparse_attention``):
masked, grouped-query flash attention, forward and backward, and the
head-mean of its probabilities.

What ``ops/flash_attention.py`` does not have, and why this is a module of
its own beside it:

* **The mask is an operand.** Which keys a query reaches is a [queries,
  keys] selection made outside (the same for every head), not a function
  of the positions. It comes in as int8 and a tile of it becomes an
  additive float32 bias (0 or -1e30) once a grid step, shared by the
  step's heads. No kept key lies after the block's last query, so key
  tiles wholly after ``first + rows - 1`` are neither fetched (their index
  maps repeat the last live tile) nor computed; ``first`` is a prefetched
  scalar, so one compiled kernel serves every block of every span of keys.
* **Grouped queries.** Query head ``c * group + i`` reads key-value head
  ``c``. A grid step holds one tile of one key-value head and walks its
  ``group`` query heads, which lie side by side in the [rows, heads * d]
  layout: nothing is transposed or broadcast in HBM.
* **A second output,** ``target[q, s] = mean over heads of exp(logit -
  logsumexp)``, float32: what the indexer is trained towards. It needs the
  finished row sums, so it is a pass of its own over the key tiles (one
  more QK product), heads innermost in the grid, accumulated in the output
  tile in VMEM.
* **One backward kernel.** A call has one block of queries, so a key
  tile's dK and dV are whole after one grid step and dQ accumulates over
  the tiles of a key-value head: the tile's probabilities are formed once
  from the saved logsumexp and feed all three (five products a tile where
  a dQ and a dK/dV kernel have seven). No gradient reaches the mask, and
  none passes through ``target``.

bf16 operands (the operands' own dtype) with float32 accumulation; logits,
running max, row sums, logsumexp and ``target`` float32; the probabilities
meet ``v`` in ``v``'s dtype. Every [rows, tile] score stays in VMEM.

The forward's logsumexp is named ``SAVED`` (``jax.ad_checkpoint.
checkpoint_name``) and is all the backward kernel reads of the forward
(``masked_attention``: how it is differentiated): a caller that recomputes
the block in its backward pass (``jax.checkpoint`` with a policy that saves
that name) runs the forward kernel once, not again in the recomputation.

The ``pallas_call``s are named ``bps_dsa_fwd`` / ``bps_dsa_probs`` /
``bps_dsa_bwd``, each under a ``jax.jit`` of its own, so a model of many
layers, spans and blocks traces each once a shape. Off-TPU they run in
interpret mode, so the CPU tests run this code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.ops.flash_attention import _resolve_interpret, pl, pltpu

FWD_NAME, PROBS_NAME, BWD_NAME = "bps_dsa_fwd", "bps_dsa_probs", "bps_dsa_bwd"
# what of a block outlives its forward pass: the logsumexp
SAVED = "bps.dsa.attended"

_VMEM = pltpu.VMEM
_NEG = -1e30
# Keys a grid step. 512 x 512 scores a head: the backward step's operands,
# accumulator and four float32 [rows, tile] temporaries stay under the 16 MB
# of VMEM a kernel gets unasked. At 1024 the forward kernel alone is 38%
# faster, but inside the Keye step the compiler wraps the call (its dK / dV
# go to VMEM) and holds it to 16 MB whatever limit the kernel asks for
# (PERF.md section 6, PR 42)
KEY_TILE = 512
F32 = jnp.float32


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract, ((), ()))),
                               preferred_element_type=F32)


def _bias(mask_ref):
    """[rows, tile] float32: 0 where the tile's mask keeps, -1e30 where
    not — added to a logit it leaves exactly the logit, or exactly -1e30."""
    return (mask_ref[...].astype(F32) - 1.0) * -_NEG


def _logits(q, k, bias, scale):
    return _dot(q, k, ((1,), (1,))) * scale + bias


def _live(ki, first_ref, rows, block_k):
    """Whether key tile ``ki`` starts at or before the block's last query."""
    return ki * block_k < first_ref[0] + rows


def _column(x, lane, g):
    """[rows, 1]: lane ``g`` of ``x`` [rows, group]."""
    return jnp.sum(jnp.where(lane == g, x, 0.0), axis=1, keepdims=True)


def _fwd_kernel(first_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_ref, l_ref, acc_ref, *, scale, group, d, block_k):
    """Grid (key-value head, key tile): online softmax over the tiles, a
    head's running max and sum in lane 0 of ``m_ref[g]`` / ``l_ref[g]``."""
    ki = pl.program_id(1)
    rows = q_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(_live(ki, first_ref, rows, block_k))
    def _compute():
        bias = _bias(mask_ref)
        k, v = k_ref[...], v_ref[...]
        for g in range(group):
            cols = slice(g * d, (g + 1) * d)
            s = _logits(q_ref[:, cols], k, bias, scale)
            m_prev = m_ref[g, :, 0:1]
            m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            # a row with no kept key in the tiles so far holds p = 1
            # throughout; the first kept key's corr = 0 wipes it
            p = jnp.exp(s - m_cur)
            corr = jnp.exp(m_prev - m_cur)
            l_new = l_ref[g, :, 0:1] * corr + p.sum(axis=-1, keepdims=True)
            l_ref[g] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            m_ref[g] = jnp.broadcast_to(m_cur, m_ref.shape[1:])
            acc_ref[:, cols] = acc_ref[:, cols] * corr + _dot(
                p.astype(v.dtype), v, ((1,), (0,)))

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        lane = jax.lax.broadcasted_iota(jnp.int32, lse_ref.shape, 1)
        lse = jnp.zeros(lse_ref.shape, F32)
        for g in range(group):
            cols = slice(g * d, (g + 1) * d)
            l = l_ref[g, :, 0:1]
            o_ref[:, cols] = (acc_ref[:, cols] / l).astype(o_ref.dtype)
            lse = jnp.where(lane == g, m_ref[g, :, 0:1] + jnp.log(l), lse)
        lse_ref[...] = lse


def _probs_kernel(first_ref, q_ref, k_ref, mask_ref, lse_ref, t_ref, *,
                  scale, group, d, block_k, heads):
    """Grid (key tile, key-value head): the tile's probabilities of every
    head, summed into the output tile; zeros where no query sees the tile."""
    ki, c = pl.program_id(0), pl.program_id(1)
    rows = q_ref.shape[0]

    @pl.when(c == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(_live(ki, first_ref, rows, block_k))
    def _compute():
        bias = _bias(mask_ref)
        k, lse = k_ref[...], lse_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, lse.shape, 1)
        total = jnp.zeros(t_ref.shape, F32)
        for g in range(group):
            s = _logits(q_ref[:, g * d:(g + 1) * d], k, bias, scale)
            total = total + jnp.exp(s - _column(lse, lane, g))
        t_ref[...] += total

        @pl.when(c == pl.num_programs(1) - 1)
        def _mean():
            t_ref[...] = t_ref[...] / heads


def _bwd_kernel(first_ref, q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                gl_ref, dq_ref, dk_ref, dv_ref, dq_acc, *, scale, group, d,
                block_k):
    """Grid (key-value head, key tile). ``gl`` is the logsumexp's
    cotangent, where flash attention has minus rowsum(dO * O). dK and dV of
    the tile are whole after its step (there is one block of queries); dQ
    accumulates over the tiles."""
    ki = pl.program_id(1)
    rows = q_ref.shape[0]
    live = _live(ki, first_ref, rows, block_k)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(live)
    def _compute():
        bias = _bias(mask_ref)
        k, v, lse, gl = k_ref[...], v_ref[...], lse_ref[...], gl_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, lse.shape, 1)
        dk = jnp.zeros(dk_ref.shape, F32)
        dv = jnp.zeros(dv_ref.shape, F32)
        for g in range(group):
            cols = slice(g * d, (g + 1) * d)
            q, do = q_ref[:, cols], do_ref[:, cols]
            p = jnp.exp(_logits(q, k, bias, scale) - _column(lse, lane, g))
            dp = _dot(do, v, ((1,), (1,)))
            ds = (p * (dp + _column(gl, lane, g)) * scale).astype(q.dtype)
            dv = dv + _dot(p.astype(do.dtype), do, ((0,), (0,)))
            dk = dk + _dot(ds, q, ((0,), (0,)))
            dq_acc[:, cols] += _dot(ds, k, ((1,), (0,)))
        dk_ref[...] = dk.astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _dead():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _keys(x, block_k):
    """k or v [s, hk, d] as the kernels read it: the heads side by side on
    the last axis, zero keys appended up to whole tiles."""
    s = x.shape[0]
    return jnp.pad(x.reshape(s, -1), ((0, (-s) % block_k), (0, 0)))


def _mask(keep, keys: int):
    """The selection as int8 over all ``keys`` (the padded sequence's):
    what lies beyond the mask's own keys is not kept."""
    return jnp.pad(keep.astype(jnp.int8),
                   ((0, 0), (0, keys - keep.shape[1])))


def _sizes(q, k):
    """(rows, query heads, key-value heads, query heads a key-value head,
    head width, keys a grid step) of q [rows, h * d] and k [s, hk, d]."""
    s, kv_heads, d = k.shape
    heads = q.shape[1] // d
    return q.shape[0], heads, kv_heads, heads // kv_heads, d, _tile(s)


def _tile(s: int) -> int:
    """Keys a grid step: ``KEY_TILE``, or all of a shorter sequence in
    whole rows of lanes."""
    return min(KEY_TILE, -(-s // 128) * 128)


def _call(kernel, name, grid, semantics, in_specs, out_specs, out_shape,
          scratch, interpret, operands):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics),
        interpret=interpret, name=name)(*operands)


def _specs(rows, group, d, block_k, heads_outer: bool):
    """The kernels' BlockSpecs by what they hold, over a grid of (key-value
    head, key tile) if ``heads_outer``, else (key tile, key-value head). A
    dead tile is fetched as the last live one, that is, not again."""
    def last_live(ki, first_ref):
        return jnp.minimum(ki, jax.lax.div(first_ref[0] + rows - 1,
                                           jnp.int32(block_k)))

    def spec(shape, index):
        if heads_outer:
            return pl.BlockSpec(shape, lambda c, ki, first: index(
                ki, c, first), memory_space=_VMEM)
        return pl.BlockSpec(shape, index, memory_space=_VMEM)

    return {
        "queries": spec((rows, group * d), lambda ki, c, first: (0, c)),
        "keys": spec((block_k, d),
                     lambda ki, c, first: (last_live(ki, first), c)),
        "keys_out": spec((block_k, d), lambda ki, c, first: (ki, c)),
        "mask": spec((rows, block_k),
                     lambda ki, c, first: (0, last_live(ki, first))),
        "rows": spec((None, rows, group), lambda ki, c, first: (c, 0, 0)),
        "target": spec((rows, block_k), lambda ki, c, first: (0, ki)),
    }


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _fwd_impl(q, k, v, keep, first, scale, interpret):
    """(out [rows, h * d] in q's dtype, logsumexp [hk, rows, group])."""
    rows, _, kv_heads, group, d, block_k = _sizes(q, k)
    k2, v2 = _keys(k, block_k), _keys(v, block_k)
    mask = _mask(keep, k2.shape[0])
    at = _specs(rows, group, d, block_k, True)
    out, lse = _call(
        functools.partial(_fwd_kernel, scale=scale, group=group, d=d,
                          block_k=block_k),
        FWD_NAME, (kv_heads, k2.shape[0] // block_k),
        ("parallel", "arbitrary"),
        [at["queries"], at["keys"], at["keys"], at["mask"]],
        [at["queries"], at["rows"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((kv_heads, rows, group), F32)],
        [_VMEM((group, rows, 128), F32), _VMEM((group, rows, 128), F32),
         _VMEM((rows, group * d), F32)],
        interpret, (first.reshape(1), q, k2, v2, mask))
    return out, lse


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _probs_impl(q, k, keep, first, lse, scale, interpret):
    """[rows, n] float32, n the mask's keys: the probabilities' mean over
    the heads."""
    rows, heads, kv_heads, group, d, block_k = _sizes(q, k)
    k2 = _keys(k, block_k)
    mask = _mask(keep, k2.shape[0])
    at = _specs(rows, group, d, block_k, False)
    target = _call(
        functools.partial(_probs_kernel, scale=scale, group=group, d=d,
                          block_k=block_k, heads=heads),
        PROBS_NAME, (k2.shape[0] // block_k, kv_heads),
        ("parallel", "arbitrary"),
        [at["queries"], at["keys"], at["mask"], at["rows"]], at["target"],
        jax.ShapeDtypeStruct(mask.shape, F32), [], interpret,
        (first.reshape(1), q, k2, mask, lse))
    return target[:, :keep.shape[1]]


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _bwd_impl(q, k, v, keep, first, lse, g, g_lse, scale, interpret):
    rows, _, kv_heads, group, d, block_k = _sizes(q, k)
    s = k.shape[0]
    k2, v2 = _keys(k, block_k), _keys(v, block_k)
    mask = _mask(keep, k2.shape[0])
    at = _specs(rows, group, d, block_k, True)
    dq, dk, dv = _call(
        functools.partial(_bwd_kernel, scale=scale, group=group, d=d,
                          block_k=block_k),
        BWD_NAME, (kv_heads, k2.shape[0] // block_k),
        ("parallel", "arbitrary"),
        [at["queries"], at["keys"], at["keys"], at["mask"], at["queries"],
         at["rows"], at["rows"]],
        [at["queries"], at["keys_out"], at["keys_out"]],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct(k2.shape, k.dtype),
         jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        [_VMEM((rows, group * d), F32)], interpret,
        (first.reshape(1), q, k2, v2, mask, g.astype(q.dtype), lse,
         _by_kv_head(g_lse.astype(F32), kv_heads)))
    return dq, dk[:s].reshape(k.shape), dv[:s].reshape(v.shape)


def _by_kv_head(x, kv_heads: int):
    """[rows, h] -> [hk, rows, group], as the kernels read a row's
    numbers: a key-value head's query heads on the lanes."""
    return x.reshape(x.shape[0], kv_heads, -1).swapaxes(0, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def masked_attention(q, k, v, keep, first, scale: float,
                     interpret: Optional[bool] = None):
    """Attention of one block of queries over the keys ``keep`` selects.

    q [rows, h * d], the heads side by side; k, v [s, hk, d] with hk
    dividing h, query head ``c * (h / hk) + i`` reading key-value head
    ``c``; keep [rows, n] bool with n
    <= s, every row keeping at least one key and none after position
    ``first + row``; ``first`` an int32 scalar, the position of the block's
    first query among the keys (a traced value: it is no part of the
    compiled kernel's shape, nor is ``n``). ``interpret`` as
    ``flash_attention`` takes it.

    Returns ``(out [rows, h * d] in q's dtype, lse [rows, h] float32,
    target [rows, n] float32)``: softmax(scale * q k^T over the kept keys)
    v, the logits' logsumexp over the kept keys, and the probabilities'
    mean over the heads, which carries no gradient. Queries and output are
    flat because that is how the kernels read and write them and how the
    projections on either side do: on a tiled device [rows, h * d] and
    [rows, h, d] are different layouts, and a value kept for the backward
    pass in both is kept twice (0.19 GB of the Keye cell's step: PERF.md
    section 6, PR 42).

    **How it is differentiated:** as ``out = sum_j exp(logit_j -
    stop_gradient(lse)) v_j`` beside ``lse``: the normaliser is a constant
    to ``out``'s gradient and reaches q and k through ``lse``'s cotangent
    alone. ``renormalised(out, lse)`` is ``out`` with the normaliser's
    gradient attached, and is what a caller differentiates; it may be
    applied to many blocks' outputs at once. That way the backward kernel
    needs no block's ``out`` (flash attention's ``rowsum(dO * O)`` arrives
    as ``-`` the cotangent of ``lse``), and a caller under
    ``jax.checkpoint`` saves a block's [rows, h] logsumexp and no second
    copy of the output its next layer keeps anyway."""
    return _attention_fwd(q, k, v, keep, first, scale, interpret)[0]


def _attention_fwd(q, k, v, keep, first, scale, interpret):
    interpret = _resolve_interpret(interpret)
    first = jnp.asarray(first, jnp.int32)
    out, lse = _fwd_impl(q, k, v, keep, first, scale, interpret)
    lse = checkpoint_name(lse, SAVED)
    target = _probs_impl(q, k, keep, first, lse, scale, interpret)
    by_row = lse.swapaxes(0, 1).reshape(q.shape[0], -1)       # [rows, h]
    return (out, by_row, target), (q, k, v, keep, first, lse)


def _attention_bwd(scale, interpret, res, cts):
    g, g_lse, _ = cts                # ``target`` is detached
    dq, dk, dv = _bwd_impl(*res, g, g_lse, scale,
                           _resolve_interpret(interpret))
    return dq, dk, dv, None, None


masked_attention.defvjp(_attention_fwd, _attention_bwd)


@jax.custom_vjp
def renormalised(out, lse):
    """``out`` [.., h * d] times ``exp(stop_gradient(lse) - lse)`` [.., h]
    head by head: ``out`` itself, to the last bit, as the function of
    ``lse`` that softmax attention's output is (``masked_attention``).
    What it keeps for its backward pass is its own output (and ``lse``,
    for the number of heads)."""
    return out


def _renormalised_fwd(out, lse):
    return out, (out, lse)


def _renormalised_bwd(res, g):
    """d ``lse`` = - the sum of ``g * out`` over a head's columns — as a
    product with the heads' indicator columns, not a sum over a [.., h, d]
    view: the compiler lays that view out anew right where ``out`` is
    made, and keeps it beside ``out`` until here (PERF.md section 6, PR
    42)."""
    out, lse = res                   # ``lse`` for the number of heads
    heads, width = lse.shape[-1], out.shape[-1]
    of_head = (jnp.arange(width)[:, None] // (width // heads)
               == jnp.arange(heads)[None, :]).astype(F32)
    dots = g.astype(F32) * out.astype(F32)
    return g, -jnp.dot(dots, of_head, precision=jax.lax.Precision.HIGHEST)


renormalised.defvjp(_renormalised_fwd, _renormalised_bwd)
