"""Pallas TPU kernels for what the chunked KDA scan needs of every chunk
before the scan (``byteps_tpu.parallel.linear_attention``, module
docstring): ``W = T (beta K e^G)``, ``U_v = T (beta V)``, ``Q e^G``,
``K e^{G_C - G}``, ``e^{G_C}`` and ``tril(P(q, k))``, with ``T = (I +
tril(beta P(k, k), -1))^-1``.

One grid step is one chunk of ``C`` tokens of all heads, read from the
``[batch, chunks, C, heads, d]`` layout as it lies: a token is a ``[heads,
d]`` tile, heads on sublanes, channels on lanes, and everything below is
elementwise over heads. Nothing but the operands leaves VMEM:

* **The pairs, one by one.** ``P(a, k)[i, j] = sum_c a_ic k_jc e^{G_ic -
  G_jc}`` for ``j <= i`` only — a subtraction, an ``exp`` of a number <= 0,
  two multiplications and a sum over lanes, float32; inside the kernel every
  pair of a chunk is formed this way, so no product between sub-chunks and
  no rounding of its operands is left. ``sub`` is how many ``j`` one
  straight-line block holds (rows above the diagonal of the block on the
  diagonal are masked to ``-inf`` before the ``exp``).
* **The triangular system, by forward substitution.** ``x_i = beta_i (b_i -
  sum_{j<i} P(k, k)[i, j] x_j)`` with ``b = [K e^G, V]``, in the same walk
  over (i, j) that forms the pairs: ``T`` is never formed, ``[W, U_v] = T
  (beta b)`` is, in float32 (the XLA form rounds ``T`` and ``beta b`` to
  ``dtype`` first).
* **The backward pass** is a second kernel, hand-written: rows from the
  last to the first, ``(I + A)^T db = dx`` by back substitution (``dA = -db
  x^T`` below the diagonal: no step of an inverse is differentiated), then
  each pair's ``exp`` formed again for the gradients of q, k and G. It reads
  ``s = b - P x`` (``x = beta s``) the forward saved.

The small products stay off the MXU on purpose: per head they are [32, 128]
x [128, 32] and [32, 32] x [32, 256], a weight load each for 32 rows; the
VPU does a chunk of 32 heads in one walk (PERF.md section 6, PR 40).

The ``pallas_call``s are named ``bps_kda_operands_fwd`` /
``bps_kda_operands_bwd``. Off-TPU they run in interpret mode, so the CPU
tests run this code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.ops.flash_attention import _resolve_interpret, pl, pltpu

FWD_NAME, BWD_NAME = "bps_kda_operands_fwd", "bps_kda_operands_bwd"

_VMEM = pltpu.VMEM
# a step holds a chunk's inputs, outputs and scratch twice over (the
# pipeline's two buffers): 12 MB forward, 22 MB backward at 32 x 32 x 128,
# over the 16 MB a kernel gets unasked
_VMEM_LIMIT = 96 * 1024 * 1024
F32 = jnp.float32


def _lane_sum(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _lane_sum_of_two(x, y):
    """One sum over lanes where the keys are as wide as the values."""
    if x.shape == y.shape:
        return _lane_sum(x + y)
    return _lane_sum(x) + _lane_sum(y)


def _column(rows, lane, i):
    """Lane ``i`` of ``rows`` [heads, C] as [heads, 1]."""
    return _lane_sum(jnp.where(lane == i, rows, 0.0))


def _masked_exp(diff, j, i, on_diagonal):
    """``e^diff`` for the pair (i, j), 0 where ``j >= i`` (only a block on
    the diagonal holds such pairs)."""
    if on_diagonal:
        diff = jnp.where(j < i, diff, -jnp.inf)
    return jnp.exp(diff)


def _cumulate(raw_ref, g_ref, chunk: int):
    """``g_ref`` [C, heads, d_k]: the log-decay ``raw_ref`` cumulated from
    the chunk's first token. Returns its last row, the chunk's whole."""
    def row(i, total):
        total = total + raw_ref[i]
        g_ref[i] = total
        return total

    return lax.fori_loop(0, chunk, row, jnp.zeros(g_ref.shape[1:], F32))


def _fwd_kernel(q_ref, k_ref, v_ref, raw_ref, bt_ref, w_ref, u_ref, qg_ref,
                kd_ref, gamma_ref, aq_ref, *rest, chunk: int, sub: int):
    *saved, xw_ref, g_ref = rest    # saved: (s_w, s_u) for a backward pass
    heads = q_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (heads, chunk), 1)
    bt = bt_ref[...]
    g_last = _cumulate(raw_ref, g_ref, chunk)
    gamma_ref[...] = jnp.exp(g_last)

    for block in range(chunk // sub):
        # a row's walk reads its whole block; a pair with j >= i weighs 0,
        # and 0 times what the buffer happened to hold may be NaN
        for j in range(block * sub, (block + 1) * sub):
            xw_ref[j] = jnp.zeros(xw_ref.shape[1:], F32)
            u_ref[j] = jnp.zeros(u_ref.shape[1:], F32)

        def row(t, _, block=block):
            i = block * sub + t
            g_i, k_i, q_i = g_ref[i], k_ref[i], q_ref[i]
            acc_w = jnp.zeros(k_i.shape, F32)
            acc_u = jnp.zeros(u_ref.shape[1:], F32)
            aq = jnp.zeros((heads, chunk), F32)
            for j in range((block + 1) * sub):
                e = _masked_exp(g_i - g_ref[j], j, i, j >= block * sub)
                kje = k_ref[j] * e
                pk = _lane_sum(k_i * kje)
                acc_w = acc_w + pk * xw_ref[j]
                acc_u = acc_u + pk * u_ref[j]
                aq = jnp.where(lane == j, _lane_sum(q_i * kje), aq)
            beta_i = _column(bt, lane, i)
            e_i = jnp.exp(g_i)
            s_w, s_u = k_i * e_i - acc_w, v_ref[i] - acc_u
            x_w = beta_i * s_w
            xw_ref[i] = x_w
            u_ref[i] = beta_i * s_u
            for ref, s_i in zip(saved, (s_w, s_u)):
                ref[i] = s_i
            w_ref[i] = x_w.astype(w_ref.dtype)
            qg_ref[i] = (q_i * e_i).astype(qg_ref.dtype)
            kd_ref[i] = (k_i * jnp.exp(g_last - g_i)).astype(kd_ref.dtype)
            aq_ref[i] = jnp.where(lane == i, _lane_sum(q_i * k_i), aq)
            return 0

        lax.fori_loop(0, sub, row, 0)


def _bwd_kernel(q_ref, k_ref, raw_ref, bt_ref, sw_ref, su_ref,
                dw_ref, du_ref, dqg_ref, dkd_ref, dgamma_ref, daq_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbt_ref,
                xw_ref, xu_ref, cw_ref, cu_ref, g_ref, *, chunk: int,
                sub: int):
    heads = q_ref.shape[1]
    lane = lax.broadcasted_iota(jnp.int32, (heads, chunk), 1)
    bt = bt_ref[...]
    g_last = _cumulate(raw_ref, g_ref, chunk)

    def prepare(i, _):
        beta_i = _column(bt, lane, i)
        xw_ref[i] = beta_i * sw_ref[i]
        xu_ref[i] = beta_i * su_ref[i]
        cw_ref[i] = jnp.zeros(cw_ref.shape[1:], F32)
        cu_ref[i] = jnp.zeros(cu_ref.shape[1:], F32)
        dk_ref[i] = jnp.zeros(dk_ref.shape[1:], F32)
        dg_ref[i] = jnp.zeros(dg_ref.shape[1:], F32)
        return 0

    lax.fori_loop(0, chunk, prepare, 0)
    carry = (dgamma_ref[...] * jnp.exp(g_last),      # into G's last row
             jnp.zeros((heads, chunk), F32))         # d beta, [heads, C]

    for block in reversed(range(chunk // sub)):
        def row(t, carry, block=block):
            dg_last, dbt = carry
            i = block * sub + sub - 1 - t
            g_i, k_i, q_i = g_ref[i], k_ref[i], q_ref[i]
            beta_i = _column(bt, lane, i)
            # the whole cotangent of x_i: the scan's, and the later rows'
            tw = dw_ref[i].astype(F32) + cw_ref[i]
            tu = du_ref[i] + cu_ref[i]
            dbt = jnp.where(lane == i, _lane_sum_of_two(
                tw * sw_ref[i], tu * su_ref[i]), dbt)
            daq_i = daq_ref[i]
            dpq_ii = _column(daq_i, lane, i)
            e_i, e_d = jnp.exp(g_i), jnp.exp(g_last - g_i)
            tw_b, tu_b = beta_i * tw, beta_i * tu     # d of b_i = [k e^G, v]
            dv_ref[i] = tu_b
            dqg_i, dkd_i = dqg_ref[i].astype(F32), dkd_ref[i].astype(F32)
            m_d = dkd_i * k_i * e_d
            dg_last = dg_last + m_d
            dk_i = tw_b * e_i + dkd_i * e_d + dpq_ii * q_i
            dq_i = dqg_i * e_i + dpq_ii * k_i
            dg_i = (tw_b * k_i + dqg_i * q_i) * e_i - m_d
            for j in range((block + 1) * sub):
                e = _masked_exp(g_i - g_ref[j], j, i, j >= block * sub)
                kje = k_ref[j] * e
                pk = _lane_sum(k_i * kje)
                dpk = -_lane_sum_of_two(tw_b * xw_ref[j], tu_b * xu_ref[j])
                # not ``daq_i[:, j:j + 1]``: a lane spread over a row of
                # lanes that way took 10 of this kernel's 15 ms
                dpq = _column(daq_i, lane, j)
                cw_ref[j] = cw_ref[j] - pk * tw_b
                cu_ref[j] = cu_ref[j] - pk * tu_b
                t_ij = dpk * k_i + dpq * q_i
                dk_ref[j] = dk_ref[j] + t_ij * e
                dk_i = dk_i + dpk * kje
                dq_i = dq_i + dpq * kje
                m = t_ij * kje
                dg_i = dg_i + m
                dg_ref[j] = dg_ref[j] - m
            dk_ref[i] = dk_ref[i] + dk_i
            dg_ref[i] = dg_ref[i] + dg_i
            dq_ref[i] = dq_i
            return dg_last, dbt

        carry = lax.fori_loop(0, sub, row, carry)

    dg_ref[chunk - 1] = dg_ref[chunk - 1] + carry[0]
    dbt_ref[...] = carry[1]

    # a token's log-decay is in every later row's cumulated one
    def uncumulate(t, total):
        total = total + dg_ref[chunk - 1 - t]
        dg_ref[chunk - 1 - t] = total
        return total

    lax.fori_loop(0, chunk, uncumulate, jnp.zeros(dg_ref.shape[1:], F32))


def _specs(shapes):
    """A ``BlockSpec`` a [batch, chunks, ...] array: one chunk a step."""
    return [pl.BlockSpec((None, None) + tuple(shape[2:]),
                         lambda b, n, rank=len(shape): (b, n) + (0,)
                         * (rank - 2), memory_space=_VMEM)
            for shape in shapes]


def _call(kernel, name, inputs, out_shapes, scratch, interpret):
    b, n = inputs[0].shape[:2]
    return pl.pallas_call(
        kernel, grid=(b, n),
        in_specs=_specs([x.shape for x in inputs]),
        out_specs=_specs([x.shape for x in out_shapes]),
        out_shape=out_shapes,
        scratch_shapes=[_VMEM(shape, F32) for shape in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)(*inputs)


@functools.partial(jax.jit, static_argnames=("sub", "dtype", "save",
                                             "interpret"))
def _fwd_impl(q, k, v, beta, g, sub, dtype, save, interpret):
    b, n, chunk, heads, d_k = q.shape
    d_v = v.shape[-1]
    if chunk % sub:
        raise ValueError(f"sub ({sub}) must divide chunk ({chunk})")

    def out(*shape, dtype=F32):
        return jax.ShapeDtypeStruct((b, n) + shape, dtype)

    keys, values = (chunk, heads, d_k), (chunk, heads, d_v)
    outs = [out(*keys, dtype=dtype), out(*values), out(*keys, dtype=dtype),
            out(*keys, dtype=dtype), out(heads, d_k),
            out(chunk, heads, chunk)]
    if save:
        outs += [out(*keys), out(*values)]
    return _call(
        functools.partial(_fwd_kernel, chunk=chunk, sub=sub),
        FWD_NAME, (q, k, v, g, beta.swapaxes(2, 3)), outs, [keys, keys],
        interpret)


@functools.partial(jax.jit, static_argnames=("sub", "interpret"))
def _bwd_impl(q, k, beta, g, s_w, s_u, cts, sub, interpret):
    b, n, chunk, heads, _ = q.shape
    keys, values = s_w.shape[2:], s_u.shape[2:]
    outs = [jax.ShapeDtypeStruct(x.shape, F32) for x in (q, k, s_u, g)]
    outs.append(jax.ShapeDtypeStruct((b, n, heads, chunk), F32))
    dq, dk, dv, dg, dbt = _call(
        functools.partial(_bwd_kernel, chunk=chunk, sub=sub), BWD_NAME,
        (q, k, g, beta.swapaxes(2, 3), s_w, s_u, *cts), outs,
        [keys, values, keys, values, keys], interpret)
    return dq, dk, dv, dbt.swapaxes(2, 3), dg


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def chunk_operands(q, k, v, beta, g, sub: int, dtype,
                   interpret: Optional[bool] = None):
    """``(W, U_v, Q e^G, K e^{G_C - G}, e^{G_C}, tril(P(q, k)))`` of every
    chunk. q, k, g [b, n, C, h, d_k] (g the log-decay of a token, <= 0: the
    kernels cumulate it inside the chunk themselves, in VMEM), v [b, n, C,
    h, d_v], beta [b, n, C, h], float32. W, Q e^G, K
    e^{G_C - G} [b, n, C, h, d_k] in ``dtype``; U_v [b, n, C, h, d_v],
    e^{G_C} [b, n, h, d_k] and the pairs [b, n, C (i), h, C (j)] float32.
    ``sub`` divides C; ``interpret`` as ``flash_attention`` takes it."""
    return tuple(_fwd_impl(q, k, v, beta, g, sub, jnp.dtype(dtype), False,
                           _resolve_interpret(interpret)))


def _operands_fwd(q, k, v, beta, g, sub, dtype, interpret):
    *outs, s_w, s_u = _fwd_impl(q, k, v, beta, g, sub, jnp.dtype(dtype),
                                True, _resolve_interpret(interpret))
    # v itself is in no gradient: it reaches the operands through s_u
    return tuple(outs), (q, k, beta, g, s_w, s_u)


def _operands_bwd(sub, dtype, interpret, res, cts):
    del dtype
    return _bwd_impl(*res, tuple(cts), sub, _resolve_interpret(interpret))


chunk_operands.defvjp(_operands_fwd, _operands_bwd)
