"""Pallas TPU kernels for what the chunked delta-rule scan needs of every
chunk before the scan where the decay is **one number a head** (Gated
DeltaNet; ``byteps_tpu.parallel.linear_attention``, module docstring):
``W = T (beta K e^G)``, ``U_v = T (beta V)``, ``Q e^G``, ``K e^{G_C - G}``,
``e^{G_C}`` and ``tril(P(q, k))`` with ``P(a, b)[i, j] = (a_i . b_j) e^{G_i
- G_j}`` and ``T = (I + tril(beta P(k, k), -1))^-1`` — ``_head_operands``'
six, written in the layout and dtypes ``ops/kda_recurrence.py`` reads
(``[b, n, C, h, d]``, a token a ``[h, d]`` tile), so that one call of the
recurrence kernels walks every chunk of a sequence over them as they lie.

One grid step is one chunk of ``C`` tokens of all heads, read from the
``[batch, chunks, C, heads, d]`` layout as ``chunked`` leaves it: a token
is a ``[heads, d]`` tile, heads on sublanes, channels on lanes, and the
walks below are elementwise over the ``h`` value heads. ``h_k`` key heads
serve ``h = h_k x groups`` of them, value head ``j groups + m`` reading key
head ``j``. Per chunk:

* **The pair products, once a key head, on the MXU.** ``k k^T`` and ``q
  k^T`` [h_k, C, C] from float32 operands split into two bf16 pieces each
  and multiplied in three passes (``hi hi + hi lo + lo hi``: what
  ``lax.Precision.HIGH``, the XLA form's ``EXACT``, asks of the chip), the
  four left-hand pieces stacked into one product; then repeated along the
  leading axis to the value heads and turned to rows ``[C (i), h, C (j)]``,
  where each value head weighs them by its own decay. The rows of q and k
  themselves go under the value heads by a strided store, every
  ``groups``-th sublane a member.
* **The decay, cumulated here.** A chunk's log-decay ``[h, C]`` (tokens on
  lanes) is cumulated by a product with a triangle of ones, its float32 in
  three exact bf16 pieces. ``e^{G_i - G_j}`` is formed for the pair itself,
  ``j > i`` masked to ``-inf`` before the ``exp``: no positive number
  exponentiated, no clamp.
* **The triangular system, by forward substitution** (``ops/kda_chunk.py``'s
  way): ``x_i = beta_i (b_i - sum_{j<i} P(k, k)[i, j] x_j)`` with ``b = [K
  e^G, V]``, rows ``[h, d]`` in float32 on the VPU, the pair's weight a
  lane of the row's ``[h, C]`` tile spread over the channels, in blocks of
  ``_ROWS`` rows (a row walks the rows of the blocks before it and of its
  own, whose pairs at and above the diagonal weigh 0; the loop over a
  block's rows is written out when the kernel is lowered). ``T`` is never
  formed.
* **The backward pass** is a second kernel, hand-written; it keeps nothing
  of the forward but its inputs. It walks the substitution again, then the
  transposed system from the last row to the first (``t_j = beta_j (dx_j -
  sum_{i>j} P(k, k)[i, j] t_i)``: ``k k^T`` is its own transpose, so the
  same tiles serve), ``dP(k, k) = -t x^T`` as one product a head on the
  MXU, and the gradients of q and k through the pair products, summed over
  a key head's value heads, as three more, all in three passes.

How the walk is written decides its speed (PERF.md section 6, PR 59; the
op forward + backward at the Qwen3-Next cell's shape against the XLA
form's 47.1 ms). A first build walked the two members of a key head's group
one after the other over a token seen as ``[h_k, groups d]`` — a reshape
that is free row-major and a copy on the chip, whose tiles are (8, 128) of
the last two axes — with rows in loops of 8: 52.0 ms. Every row and pair
written out in Python: 18.0 ms, and 8.3 s of tracing and lowering in every
process that builds the step. Rows, or rows and pairs, in loops: 30.4 ms,
a row's lane sums never under the row before's arithmetic. This one writes
a block's pairs out in Python and lets the lowering write out the loops
over a block's rows (``_written_out``): the line Mosaic schedules is straight, 576
pairs for 496, and it is traced once a block.

The ``pallas_call``s are named ``bps_gdn_operands_fwd`` /
``bps_gdn_operands_bwd``. Off-TPU they run in interpret mode, so the CPU
tests run this code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.ops.flash_attention import _resolve_interpret, pl, pltpu
from byteps_tpu.ops.kda_chunk import (F32, _VMEM, _column, _lane_sum,
                                      _lane_sum_of_two, _specs)
from byteps_tpu.ops.kda_recurrence import _NN, _NT, _TN, _dot, _turned

FWD_NAME, BWD_NAME = "bps_gdn_operands_fwd", "bps_gdn_operands_bwd"

BF16 = jnp.bfloat16
# A step holds a chunk's blocks twice over (the pipeline's two buffers) and
# its scratch, all of them rows of a token and head: about 8 MB forward, 20
# MB backward at 16 key heads under 32 value heads of 128, chunks of 32,
# over the 16 MB a kernel gets unasked (the compiler counts 45.8 MB for the
# backward kernel at chunks of 64). Asked for by the rows a chunk holds, 32
# MiB at the Qwen3-Next cell's shape, and never all of it: what a call asks
# of VMEM shows in the step's HBM peak (PERF.md section 6, PR 57).
# ``linear_attention.py::HEAD_KERNEL_ROWS`` keeps the ask within that.
_VMEM_A_ROW = 32 * 1024
# The rows of a block of the substitution: a chunk's blocks are written out
# one after the other, a block's rows are a loop that the lowering writes
# out (``_written_out``), and a row's pairs are written out again — every row of
# the blocks before it and of its own, where the pairs at and above the
# diagonal weigh 0: at chunks of 32, 144 pair bodies traced and 576 pairs
# run for the 496 below the diagonal. On the chip, the operand kernels
# forward | backward at the Qwen3-Next cell's shape: blocks of 8 rows 4.45 |
# 12.70 ms, of 4 4.17 | 12.14, of 2 4.02 | 11.91 with twice the tracing
# (and 9.40 backward at 4 once the loop over the pairs' rows was written
# out too); the same rows as loops 6.47 | 17.98; a pair's weight picked as a
# lane slice, not as a masked lane sum, 5.09 | 13.83 (PERF.md section 6, PR
# 59).
_ROWS = 4


def _pieces(x, n: int):
    """``x`` (float32) as ``n`` bf16 pieces, largest first, that sum to it:
    two carry 16 bits of it, three all 24."""
    out = []
    for _ in range(n):
        out.append(x.astype(BF16))
        x = x - out[-1].astype(F32)
    return out


def _triangle(chunk: int, later: bool):
    """[C, C] of ones where ``row <= column`` (``later``: ``>=``), bf16."""
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return (row >= col if later else row <= col).astype(BF16)


def _summed_along_lanes(x, later: bool = False):
    """``x`` [h_k, C] cumulated from its first lane (``later``: from each
    lane to the last), exact to float32: three bf16 pieces against ones."""
    ones = _triangle(x.shape[1], later)
    return sum(jnp.dot(p, ones, preferred_element_type=F32)
               for p in _pieces(x, 3))


def _repeated(x, groups: int):
    """[h_k, ...] -> [h_k groups, ...]: key head j under each of its value
    heads ``j groups .. (j + 1) groups - 1`` (a leading axis: no data
    moves inside a tile)."""
    if groups == 1:
        return x
    return jnp.broadcast_to(x[:, None], (x.shape[0], groups) + x.shape[1:]
                            ).reshape(x.shape[0] * groups, *x.shape[1:])


def _pair_products(q_ref, k_ref, kk_ref, qk_ref, groups: int):
    """``k k^T`` and ``q k^T``, once a key head in three bf16 passes, into
    ``kk_ref`` / ``qk_ref`` [C (i), h, C (j)]: row i a ``[h, C]`` tile, a
    key head's products under each of its value heads."""
    chunk = k_ref.shape[0]
    k_hi, k_lo = _pieces(_turned(k_ref[...]), 2)        # [h_k, C, d_k]
    q_hi, q_lo = _pieces(_turned(q_ref[...]), 2)
    by_hi = _dot(jnp.concatenate([k_hi, k_lo, q_hi, q_lo], axis=1), k_hi, _NT)
    by_lo = _dot(jnp.concatenate([k_hi, q_hi], axis=1), k_lo, _NT)
    kk_ref[...] = _turned(_repeated(
        by_hi[:, :chunk] + by_hi[:, chunk:2 * chunk] + by_lo[:, :chunk],
        groups))
    qk_ref[...] = _turned(_repeated(
        by_hi[:, 2 * chunk:3 * chunk] + by_hi[:, 3 * chunk:]
        + by_lo[:, chunk:], groups))


def _under_value_heads(ref, rows_ref, groups: int):
    """The ref whose row i is ``ref``'s [h_k, d] under the value heads, [h,
    d]: ``ref`` itself where each key head has one, else ``rows_ref``
    filled, member by member, on every ``groups``-th sublane."""
    if groups == 1:
        return ref
    for m in range(groups):
        rows_ref[:, pl.ds(m, ref.shape[1], stride=groups), :] = ref[...]
    return rows_ref


def _summed_over_members(ref, groups: int):
    """[C, h, d] -> [C, h_k, d]: a key head's sum over its value heads."""
    if groups == 1:
        return ref[...]
    h_k = ref.shape[1] // groups
    return sum(ref[:, pl.ds(m, h_k, stride=groups), :] for m in range(groups))


def _decay(gt, g_i, lane, i: int):
    """``e^{G_i - G_j}`` on lane j of row i, 0 where ``j > i``."""
    return jnp.exp(jnp.where(lane <= i, g_i - gt, -jnp.inf))


def _substitute(chunk, lane, gt, bt, kk_ref, k_rows, v_ref, xw_ref, xu_ref,
                row_done, unroll: bool):
    """Forward substitution over a chunk's rows: ``x_i = beta_i s_i``, ``s_i
    = b_i - sum_{j<i} P(k, k)[i, j] x_j``, ``b_i = [k_i e^{G_i}, v_i]``, a
    row ``[h, d]``, in blocks of ``_ROWS`` rows. ``x`` lands in ``xw_ref``
    / ``xu_ref``; ``row_done(i, g_i, decay, s_w, s_u, x_w)`` does the rest
    of a row."""
    zero_w = jnp.zeros(xw_ref.shape[1:], F32)
    zero_u = jnp.zeros(xu_ref.shape[1:], F32)
    for first in range(0, chunk, _ROWS):
        # a row's walk reads its whole block; a pair with j >= i weighs 0,
        # and 0 times what the buffer happened to hold may be NaN
        for j in range(first, first + _ROWS):
            xw_ref[j] = zero_w
            xu_ref[j] = zero_u

        def row(t, _, first=first):
            i = first + t
            g_i = _column(gt, lane, i)
            decay = _decay(gt, g_i, lane, i)
            pk = jnp.where(lane < i, kk_ref[i] * decay, 0.0)
            s_w, s_u = k_rows[i] * jnp.exp(g_i), v_ref[i]
            for j in range(first + _ROWS):
                p = _column(pk, lane, j)
                s_w = s_w - p * xw_ref[j]
                s_u = s_u - p * xu_ref[j]
            beta_i = _column(bt, lane, i)
            x_w = beta_i * s_w
            xw_ref[i] = x_w
            xu_ref[i] = beta_i * s_u
            row_done(i, g_i, decay, s_w, s_u, x_w)
            return 0

        lax.fori_loop(0, _ROWS, row, 0, unroll=unroll)


def _fwd_kernel(q_ref, k_ref, v_ref, raw_ref, bt_ref, w_ref, u_ref, qg_ref,
                kd_ref, gamma_ref, aq_ref, kk_ref, qk_ref, xw_ref, *rows,
                chunk: int, groups: int, unroll: bool):
    heads = v_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (heads, chunk), 1)
    _pair_products(q_ref, k_ref, kk_ref, qk_ref, groups)
    k_rows = _under_value_heads(k_ref, rows[0] if rows else None, groups)
    q_rows = _under_value_heads(q_ref, rows[1] if rows else None, groups)
    gt = _summed_along_lanes(raw_ref[...])              # G, tokens on lanes
    g_last = _column(gt, lane, chunk - 1)
    gamma_ref[...] = jnp.exp(g_last)

    def row_done(i, g_i, decay, s_w, s_u, x_w):
        del s_w, s_u
        w_ref[i] = x_w.astype(w_ref.dtype)
        qg_ref[i] = (q_rows[i] * jnp.exp(g_i)).astype(qg_ref.dtype)
        kd_ref[i] = (k_rows[i] * jnp.exp(g_last - g_i)).astype(kd_ref.dtype)
        aq_ref[i] = (qk_ref[i] * decay).astype(aq_ref.dtype)

    # U_v leaves in float32: its block is the substitution's own store
    _substitute(chunk, lane, gt, bt_ref[...], kk_ref, k_rows, v_ref, xw_ref,
                u_ref, row_done, unroll)


def _bwd_kernel(q_ref, k_ref, v_ref, raw_ref, bt_ref, dw_ref, du_ref,
                dqg_ref, dkd_ref, dgamma_ref, daq_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbt_ref,
                kk_ref, qk_ref, dkk_ref, dqk_ref, sw_ref, xw_ref, tw_ref,
                dkv_ref, dqv_ref, su_ref, xu_ref, tu_ref, *rows, chunk: int,
                groups: int, unroll: bool):
    heads = v_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (heads, chunk), 1)
    _pair_products(q_ref, k_ref, kk_ref, qk_ref, groups)
    k_rows = _under_value_heads(k_ref, rows[0] if rows else None, groups)
    q_rows = _under_value_heads(q_ref, rows[1] if rows else None, groups)
    gt, bt = _summed_along_lanes(raw_ref[...]), bt_ref[...]
    g_last = _column(gt, lane, chunk - 1)

    # the forward's walk again: s = b - P x and x = beta s of every row
    def keep(i, g_i, decay, s_w, s_u, x_w):
        del g_i, decay, x_w
        sw_ref[i] = s_w
        su_ref[i] = s_u

    _substitute(chunk, lane, gt, bt, kk_ref, k_rows, v_ref, xw_ref, xu_ref,
                keep, unroll)

    # the transposed system, from the last row: t_j = beta_j c_j, c_j = dx_j
    # - sum_{i>j} P(k, k)[i, j] t_i, and with t what a row's own operands
    # hand back
    carry = (jnp.zeros((heads, chunk), F32),            # d beta, [h, C]
             jnp.zeros((heads, chunk), F32),            # d G
             jnp.zeros((heads, 1), F32))                # into G's last row
    for first in reversed(range(0, chunk, _ROWS)):
        for i in range(first, first + _ROWS):
            tw_ref[i] = jnp.zeros(tw_ref.shape[1:], F32)
            tu_ref[i] = jnp.zeros(tu_ref.shape[1:], F32)

        def row(back, carry, first=first):
            dbt, dg, dg_last = carry
            j = first + _ROWS - 1 - back
            g_j = _column(gt, lane, j)
            # P(k, k)[i, j] on lane i: k k^T is its own transpose
            pk = jnp.where(lane > j, kk_ref[j] * jnp.exp(jnp.where(
                lane >= j, gt - g_j, -jnp.inf)), 0.0)
            c_w, c_u = dw_ref[j].astype(F32), du_ref[j]
            for i in range(first, chunk):
                p = _column(pk, lane, i)
                c_w = c_w - p * tw_ref[i]
                c_u = c_u - p * tu_ref[i]
            dbt = jnp.where(lane == j, _lane_sum_of_two(
                c_w * sw_ref[j], c_u * su_ref[j]), dbt)
            beta_j = _column(bt, lane, j)
            t_w, t_u = beta_j * c_w, beta_j * c_u
            tw_ref[j] = t_w
            tu_ref[j] = t_u
            dv_ref[j] = t_u
            k_j, q_j = k_rows[j], q_rows[j]
            e_j, e_d = jnp.exp(g_j), jnp.exp(g_last - g_j)
            dqg_j, dkd_j = dqg_ref[j].astype(F32), dkd_ref[j].astype(F32)
            m_d = dkd_j * k_j * e_d
            dkv_ref[j] = t_w * e_j + dkd_j * e_d
            dqv_ref[j] = dqg_j * e_j
            dg = jnp.where(lane == j, _lane_sum(
                (t_w * k_j + dqg_j * q_j) * e_j - m_d), dg)
            return dbt, dg, dg_last + _lane_sum(m_d)

        carry = lax.fori_loop(0, _ROWS, row, carry, unroll=unroll)
    dbt, dg, dg_last = carry

    # dP(k, k) = -t x^T below the diagonal: one product a head
    t_hi, t_lo = _pieces(_turned(jnp.concatenate(
        [tw_ref[...], tu_ref[...]], axis=2)), 2)        # [h, C, d_k + d_v]
    x_hi, x_lo = _pieces(_turned(jnp.concatenate(
        [xw_ref[...], xu_ref[...]], axis=2)), 2)
    by_hi = _dot(jnp.concatenate([t_hi, t_lo], axis=1), x_hi, _NT)
    dkk_ref[...] = _turned(-(by_hi[:, :chunk] + by_hi[:, chunk:]
                             + _dot(t_hi, x_lo, _NT)))

    # through the decays: each pair's weight back to the products and to
    # G_i - G_j
    def pair_row(i, carry):
        dg, columns = carry
        decay = _decay(gt, _column(gt, lane, i), lane, i)
        dpk = jnp.where(lane < i, dkk_ref[i] * decay, 0.0)
        dpq = daq_ref[i].astype(F32) * decay
        dkk_ref[i] = dpk
        dqk_ref[i] = dpq
        weight = dpk * kk_ref[i] + dpq * qk_ref[i]
        return (dg + jnp.where(lane == i, _lane_sum(weight), 0.0),
                columns + weight)

    dg, columns = lax.fori_loop(0, chunk, pair_row,
                                (dg, jnp.zeros((heads, chunk), F32)),
                                unroll=unroll)
    dg = dg - columns + jnp.where(
        lane == chunk - 1, dg_last + dgamma_ref[...] * jnp.exp(g_last), 0.0)
    # a token's log-decay is in every later row's cumulated one
    dg_ref[...] = _summed_along_lanes(dg, later=True)
    dbt_ref[...] = dbt

    # q and k through the pair products, a key head's sum over its value
    # heads: dq += dQK k, dk += (dKK + dKK^T) k + dQK^T q
    def of_key_heads(ref):
        x = _turned(ref[...])                           # [h, C (i), C (j)]
        return x.reshape(heads // groups, groups, chunk, chunk).sum(1)

    dkk_hi, dkk_lo = _pieces(of_key_heads(dkk_ref), 2)
    dqk_hi, dqk_lo = _pieces(of_key_heads(dqk_ref), 2)
    k_hi, k_lo = _pieces(_turned(k_ref[...]), 2)
    q_hi, q_lo = _pieces(_turned(q_ref[...]), 2)
    by_hi = _dot(jnp.concatenate([dkk_hi, dkk_lo, dqk_hi, dqk_lo], axis=1),
                 k_hi, _NN)
    by_lo = _dot(jnp.concatenate([dkk_hi, dqk_hi], axis=1), k_lo, _NN)
    rows_hi = jnp.concatenate([dkk_hi, dqk_hi], axis=1)  # [h_k, 2C (i), C]
    rows_lo = jnp.concatenate([dkk_lo, dqk_lo], axis=1)
    kq_hi = jnp.concatenate([k_hi, q_hi], axis=1)        # [h_k, 2C (i), d_k]
    kq_lo = jnp.concatenate([k_lo, q_lo], axis=1)
    transposed = (_dot(rows_hi, kq_hi, _TN) + _dot(rows_hi, kq_lo, _TN)
                  + _dot(rows_lo, kq_hi, _TN))
    dk_ref[...] = _summed_over_members(dkv_ref, groups) + _turned(
        by_hi[:, :chunk] + by_hi[:, chunk:2 * chunk] + by_lo[:, :chunk]
        + transposed)
    dq_ref[...] = _summed_over_members(dqv_ref, groups) + _turned(
        by_hi[:, 2 * chunk:3 * chunk] + by_hi[:, 3 * chunk:]
        + by_lo[:, chunk:])


def _written_out(interpret: bool) -> bool:
    """Whether the walks' loops (a block's rows, the pairs' rows) are
    written out when the kernel is lowered — ``fori_loop(unroll=True)``,
    traced once: Mosaic then schedules one straight line of constant
    indices, a row's lane sums under the row before's arithmetic. Compiled
    they are; the interpreter keeps the loops, whose every copy XLA would
    otherwise compile for the CPU (three minutes a test)."""
    return not interpret


def _call(kernel, name, inputs, out_shapes, scratch, interpret):
    b, n, chunk = inputs[0].shape[:3]
    heads = inputs[2].shape[3]
    return pl.pallas_call(
        functools.partial(kernel, unroll=_written_out(interpret)),
        grid=(b, n), in_specs=_specs([x.shape for x in inputs]),
        out_specs=_specs([x.shape for x in out_shapes]),
        out_shape=out_shapes,
        scratch_shapes=[_VMEM(shape, F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(16 * 1024 * 1024,
                                 _VMEM_A_ROW * chunk * heads)),
        interpret=interpret, name=name)(*inputs)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def _fwd_impl(q, k, v, beta, g, dtype, interpret):
    b, n, chunk, h_k, d_k = q.shape
    heads, d_v = v.shape[3:]

    def out(*shape, dtype=F32):
        return jax.ShapeDtypeStruct((b, n) + shape, dtype)

    keys = (chunk, heads, d_k)
    pair_rows = (chunk, heads, chunk)
    return tuple(_call(
        functools.partial(_fwd_kernel, chunk=chunk, groups=heads // h_k),
        FWD_NAME, (q, k, v, g.swapaxes(2, 3), beta.swapaxes(2, 3)),
        [out(*keys, dtype=dtype), out(chunk, heads, d_v),
         out(*keys, dtype=dtype), out(*keys, dtype=dtype), out(heads, 1),
         out(*pair_rows, dtype=dtype)],
        [pair_rows] * 2 + [keys] * (1 if heads == h_k else 3), interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _bwd_impl(q, k, v, beta, g, cts, interpret):
    b, n, chunk, h_k, d_k = q.shape
    heads, d_v = v.shape[3:]
    pair_rows, keys, values = ((chunk, heads, chunk), (chunk, heads, d_k),
                               (chunk, heads, d_v))
    tokens = jax.ShapeDtypeStruct((b, n, heads, chunk), F32)
    dq, dk, dv, dg, dbt = _call(
        functools.partial(_bwd_kernel, chunk=chunk, groups=heads // h_k),
        BWD_NAME, (q, k, v, g.swapaxes(2, 3), beta.swapaxes(2, 3), *cts),
        [jax.ShapeDtypeStruct(q.shape, F32),
         jax.ShapeDtypeStruct(k.shape, F32),
         jax.ShapeDtypeStruct(v.shape, F32), tokens, tokens],
        [pair_rows] * 4 + [keys] * 5 + [values] * 3
        + [keys] * (0 if heads == h_k else 2), interpret)
    return dq, dk, dv, dbt.swapaxes(2, 3), dg.swapaxes(2, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def head_operands(q, k, v, beta, g, dtype,
                  interpret: Optional[bool] = None):
    """``(W, U_v, Q e^G, K e^{G_C - G}, e^{G_C}, tril(P(q, k)))`` of every
    chunk under one decay a head. q, k [b, n, C, h_k, d_k], v [b, n, C, h,
    d_v] (``h_k`` divides h: key head j serves value heads ``j groups .. (j
    + 1) groups - 1``), beta and g [b, n, C, h] (g the log-decay of a token,
    <= 0: the kernels cumulate it inside the chunk themselves), float32. W,
    Q e^G, K e^{G_C - G} [b, n, C, h, d_k] and the pairs [b, n, C (i), h, C
    (j)] in ``dtype``; U_v [b, n, C, h, d_v] and e^{G_C} [b, n, h, 1]
    float32: what ``ops/kda_recurrence.py::recurrence`` takes.
    ``interpret`` as ``flash_attention`` takes it."""
    return _fwd_impl(q, k, v, beta, g, jnp.dtype(dtype),
                     _resolve_interpret(interpret))


def _operands_fwd(q, k, v, beta, g, dtype, interpret):
    # nothing of the forward is kept: the backward kernel walks it again
    return _fwd_impl(q, k, v, beta, g, jnp.dtype(dtype),
                     _resolve_interpret(interpret)), (q, k, v, beta, g)


def _operands_bwd(dtype, interpret, res, cts):
    del dtype
    return _bwd_impl(*res, tuple(cts), _resolve_interpret(interpret))


head_operands.defvjp(_operands_fwd, _operands_bwd)
