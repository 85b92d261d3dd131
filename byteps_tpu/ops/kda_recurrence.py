"""Pallas TPU kernels for the scan over chunks in the chunked delta-rule
scan (``byteps_tpu.parallel.linear_attention._recurrence``, whose arithmetic
this is letter for letter): from the state ``S`` [d_k, d_v] a head, chunk
after chunk,

    U = U_v - W S,    O = Q_g S + A_q U,    S <- Diag(gamma) S + K_d^T U.

As XLA runs it that is a ``while`` iteration of small ops a chunk, the
float32 state (2 MB at 32 heads of 128 x 128) read from and written to HBM
around every product, under a second ``while`` over groups of chunks that
slices and copies every operand on its way to a group, after a copy that
lays them out chunk-major with heads before tokens. Here the operands stay
as ``ops/kda_chunk.py`` leaves them, [batch, chunks, C, heads, d] with a
token a ``[heads, d]`` tile, and the grid is (batch, blocks of heads,
chunks), the chunk axis sequential: the state of a block of heads lives in
a float32 VMEM scratch from the call's first chunk to its last, is read
from HBM once and written once, and a chunk's operands stream through the
pipeline's buffers from where they lie; a block ``[C, heads, d]`` is turned
to ``[heads, C, d]`` in VMEM (``_turned``). One call holds every chunk of a
sequence.

The scratch holds the state transposed, ``S^T`` [d_v, d_k]: the decay of a
key channel is then a row of lanes broadcast over sublanes, ``W S`` and
``Q_g S`` are products against a transposed right-hand side (as a flash
kernel's ``q k^T``) and share their stationary operand, so they are one
``[2C, d_k] x [d_k, d_v]`` product a head. The arithmetic is the XLA
body's, in its order and precisions: the state and ``U`` are rounded to
``dtype`` only as matmul operands, every product accumulates in float32 and
the carried state is never rounded. The heads of a block go through each
product together, one batched call: head by head a chunk is a chain of
three small products, each waiting for the one before, and the kernel was
slower than XLA (PERF.md section 6, PR 54).

**The backward pass** is a second kernel, hand-written. The forward kernel
under ``jax.grad`` leaves the (transposed) state at the entry of every inner
group of ``_INNER`` chunks — what the XLA scan over groups kept, one state a
group; with the operands they are the residuals. The backward kernel's grid
is (batch, blocks of heads, inner groups from the last to the first, twice
an inner group's chunks): the first half of a group walks its chunks forward
from its entry state and leaves every chunk's state (float32, 64 KB a head)
and ``U`` in VMEM scratch, the second walks them from the last to the first
carrying ``dS`` in VMEM, over the groups too:

    dU = A_q^T dO + K_d dS'         dW = -dU S^T       dQ_g = dO S^T
    dK_d = U dS'^T    dA_q = dO U^T    dgamma = rowsum(dS' . S)
    dS = Diag(gamma) dS' + Q_g^T dO - W^T dU

with ``[dU; dO]`` stacked for the two products that share ``S``. The block
of heads is sized so that an inner group's states fit (``_head_block``).

The ``pallas_call``s are named ``bps_kda_recurrence_fwd`` /
``bps_kda_recurrence_bwd``. Off-TPU they run in interpret mode, so the CPU
tests run this code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.ops.flash_attention import _resolve_interpret, pl, pltpu

FWD_NAME, BWD_NAME = "bps_kda_recurrence_fwd", "bps_kda_recurrence_bwd"

_VMEM = pltpu.VMEM
# the backward kernel's states alone may be 32 MB, over the 16 MB a kernel
# gets unasked with its operands' buffers beside them
_VMEM_LIMIT = 96 * 1024 * 1024
# ... which bounds the block of heads: the states of an inner group, [d_v,
# d_k] float32 a chunk and head
_STATES_BYTES = 32 * 1024 * 1024
# The chunks of an inner group: the backward kernel keeps that many states
# a head in VMEM, and the forward kernel one state in HBM for every such
# group (what the XLA scan over groups kept: one state a group).
_INNER = 16
F32 = jnp.float32

# contractions of two stacks of matrices, one a head: plain, against a
# transposed right-hand side, over the rows of both
_NN, _NT, _TN = ((2,), (1,)), ((2,), (2,)), ((1,), (1,))


def _dot(x, y, contract):
    return lax.dot_general(x, y, (contract, ((0,), (0,))),
                           preferred_element_type=F32)


def _turned(x):
    """[C, heads, d] <-> [heads, C, d]: a chunk's block as it lies in HBM
    (a token is a [heads, d] tile) and as the products want it."""
    return jnp.swapaxes(x, 0, 1)


def _fwd_kernel(s0_ref, w_ref, u_ref, q_ref, k_ref, gamma_ref, a_ref,
                s1_ref, o_ref, *rest, inner: int):
    *entries, st_ref = rest     # entries: where a backward pass will start
    n = pl.program_id(2)
    chunk = w_ref.shape[0]
    dtype = w_ref.dtype

    @pl.when(n == 0)
    def _():
        st_ref[...] = jnp.swapaxes(s0_ref[...], 1, 2)

    for entry_ref in entries:
        @pl.when(n % inner == 0)
        def _():
            entry_ref[...] = st_ref[...]

    s = st_ref[...]                                     # S^T [., d_v, d_k]
    both = _dot(jnp.concatenate([_turned(w_ref[...]), _turned(q_ref[...])],
                                axis=1), s.astype(dtype), _NT)
    u = (_turned(u_ref[...]) - both[:, :chunk]).astype(dtype)   # U_v - W S
    o_ref[...] = _turned(both[:, chunk:]
                         + _dot(_turned(a_ref[...]), u, _NN))   # Q_g S + ..
    st_ref[...] = (gamma_ref[...][:, None, :] * s
                   + _dot(u, _turned(k_ref[...]), _TN))

    @pl.when(n == pl.num_programs(2) - 1)
    def _():
        s1_ref[...] = jnp.swapaxes(st_ref[...], 1, 2)


def _bwd_kernel(entry_ref, w_ref, u_ref, q_ref, k_ref, gamma_ref, a_ref,
                do_ref, ds1_ref, ds0_ref, dw_ref, du_ref, dq_ref, dk_ref,
                dgamma_ref, da_ref, states_ref, us_ref, dst_ref):
    r, t = pl.program_id(2), pl.program_id(3)
    g = pl.num_programs(3) // 2
    chunk = w_ref.shape[0]
    dtype = w_ref.dtype

    @pl.when(t == 0)
    def _():
        states_ref[0] = entry_ref[...]

    @pl.when(t < g)
    def _():            # forward again: chunk t's U and the state it leaves
        s = states_ref[t]
        u = (_turned(u_ref[...]) - _dot(_turned(w_ref[...]), s.astype(dtype),
                                        _NT)).astype(dtype)
        us_ref[t] = u

        @pl.when(t < g - 1)
        def _():
            states_ref[t + 1] = (gamma_ref[...][:, None, :] * s
                                 + _dot(u, _turned(k_ref[...]), _TN))

    @pl.when((r == 0) & (t == g))
    def _():
        dst_ref[...] = jnp.swapaxes(ds1_ref[...], 1, 2)

    @pl.when(t >= g)
    def _():            # chunk 2g - 1 - t, under dS' of the chunk after it
        c = 2 * g - 1 - t
        s, ds, u = states_ref[c], dst_ref[...], us_ref[c]
        ds_ = ds.astype(dtype)
        do = _turned(do_ref[...]).astype(dtype)
        w, q, k = (_turned(ref[...]) for ref in (w_ref, q_ref, k_ref))
        du = _dot(_turned(a_ref[...]), do, _TN) + _dot(k, ds_, _NT)
        du_ref[...] = _turned(du)
        both = jnp.concatenate([du.astype(dtype), do], axis=1)
        back = _dot(both, s.astype(dtype), _NN)         # [dU; dO] S^T
        dw_ref[...] = _turned(-back[:, :chunk]).astype(dw_ref.dtype)
        dq_ref[...] = _turned(back[:, chunk:]).astype(dq_ref.dtype)
        dk_ref[...] = _turned(_dot(u, ds_, _NN)).astype(dk_ref.dtype)
        da_ref[...] = _turned(_dot(do, u, _NT)).astype(da_ref.dtype)
        dgamma_ref[...] = jnp.sum(ds * s, axis=1)
        dst_ref[...] = gamma_ref[...][:, None, :] * ds + _dot(
            both, jnp.concatenate([-w, q], axis=1), _TN)

    @pl.when((r == pl.num_programs(2) - 1) & (t == 2 * g - 1))
    def _():
        ds0_ref[...] = jnp.swapaxes(dst_ref[...], 1, 2)


def _head_block(heads: int, inner: int, d_k: int, d_v: int) -> int:
    """Heads a grid step: as many as keep the backward kernel's ``inner``
    states under ``_STATES_BYTES``, in whole sublane groups (a token of a
    block is a [heads, d] tile) unless that is every head."""
    block = _STATES_BYTES // (inner * d_k * d_v * 4)
    return heads if block >= heads else max(8, block // 8 * 8)


def _state_spec(shape, block: int, group_of=None):
    """``block`` heads of a [b, h, ., .] array, or of group ``group_of(*the
    grid's further axes)`` of a [groups, b, h, ., .] one."""
    if group_of is None:
        return pl.BlockSpec((None, block) + tuple(shape[2:]),
                            lambda i, j, *_: (i, j, 0, 0), memory_space=_VMEM)
    return pl.BlockSpec(
        (None, None, block) + tuple(shape[3:]),
        lambda i, j, *at: (group_of(*at), i, j, 0, 0), memory_space=_VMEM)


def _chunk_spec(shape, block: int, chunk_of):
    """``block`` heads of the chunk ``chunk_of(*the grid's further axes)``
    of a [b, n, C, h, d] array, or of a [b, n, h, d_k] one (the decays)."""
    if len(shape) == 4:
        return pl.BlockSpec(
            (None, None, block, shape[3]),
            lambda i, j, *at: (i, chunk_of(*at), j, 0), memory_space=_VMEM)
    return pl.BlockSpec(
        (None, None, shape[2], block, shape[4]),
        lambda i, j, *at: (i, chunk_of(*at), 0, j, 0), memory_space=_VMEM)


def _params(*sequential):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") + sequential,
        vmem_limit_bytes=_VMEM_LIMIT)


def _tiling(w, u_v, inner, head_block):
    """(chunks an inner group, heads a grid step)."""
    _, n, _, heads, d_k = w.shape
    inner = inner or next(d for d in range(min(n, _INNER), 0, -1)
                          if n % d == 0)
    return inner, head_block or _head_block(heads, inner, d_k, u_v.shape[-1])


@functools.partial(jax.jit, static_argnames=("save", "inner", "head_block",
                                             "interpret"))
def _scan_fwd(state, w, u_v, q_g, k_d, gamma, a_q, save, inner, head_block,
              interpret):
    b, n, _, heads, d_k = w.shape
    d_v = u_v.shape[-1]
    inner, block = _tiling(w, u_v, inner, head_block)
    chunked = (w, u_v, q_g, k_d, gamma, a_q)
    outs = [jax.ShapeDtypeStruct(state.shape, F32),
            jax.ShapeDtypeStruct(u_v.shape, F32)]
    out_specs = [_state_spec(state.shape, block),
                 _chunk_spec(u_v.shape, block, lambda t: t)]
    if save:        # the transposed state at every inner group's entry
        outs.append(jax.ShapeDtypeStruct((n // inner, b, heads, d_v, d_k),
                                         F32))
        out_specs.append(_state_spec(outs[-1].shape, block,
                                     lambda t: t // inner))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, inner=inner),
        grid=(b, pl.cdiv(heads, block), n),
        in_specs=[_state_spec(state.shape, block)] + [
            _chunk_spec(x.shape, block, lambda t: t) for x in chunked],
        out_specs=out_specs, out_shape=outs,
        scratch_shapes=[_VMEM((block, d_v, d_k), F32)],
        compiler_params=_params("arbitrary"), interpret=interpret,
        name=FWD_NAME)(state, *chunked)


@functools.partial(jax.jit, static_argnames=("inner", "head_block",
                                             "interpret"))
def _scan_bwd(entries, w, u_v, q_g, k_d, gamma, a_q, d_o, d_state, inner,
              head_block, interpret):
    b, n, chunk, heads, d_k = w.shape
    d_v = u_v.shape[-1]
    inner, block = _tiling(w, u_v, inner, head_block)
    groups = n // inner

    # the inner groups from the last to the first; in each, forward over
    # its chunks, then back
    def both(r, t):
        return (groups - 1 - r) * inner + jnp.where(
            t < inner, t, 2 * inner - 1 - t)

    def second(r, t):   # held at the group's last chunk until the walk back
        return (groups - 1 - r) * inner + jnp.minimum(
            inner - 1, 2 * inner - 1 - t)

    reads = ((w, both), (u_v, both), (q_g, second), (k_d, both),
             (gamma, both), (a_q, second), (d_o, second))
    grads = (w, u_v, q_g, k_d, gamma, a_q)
    return pl.pallas_call(
        _bwd_kernel, grid=(b, pl.cdiv(heads, block), groups, 2 * inner),
        in_specs=[_state_spec(entries.shape, block,
                              lambda r, t: groups - 1 - r)]
        + [_chunk_spec(x.shape, block, at) for x, at in reads]
        + [_state_spec(d_state.shape, block)],
        out_specs=[_state_spec(d_state.shape, block)] + [
            _chunk_spec(x.shape, block, second) for x in grads],
        out_shape=[jax.ShapeDtypeStruct(d_state.shape, F32)] + [
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in grads],
        scratch_shapes=[_VMEM((inner, block, d_v, d_k), F32),
                        _VMEM((inner, block, chunk, d_v), w.dtype),
                        _VMEM((block, d_v, d_k), F32)],
        compiler_params=_params("arbitrary", "arbitrary"),
        interpret=interpret, name=BWD_NAME)(
            entries, w, u_v, q_g, k_d, gamma, a_q, d_o, d_state)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan(state, w, u_v, q_g, k_d, gamma, a_q, inner, head_block, interpret):
    return tuple(_scan_fwd(state, w, u_v, q_g, k_d, gamma, a_q, False, inner,
                           head_block, _resolve_interpret(interpret)))


def _scan_vjp_fwd(state, w, u_v, q_g, k_d, gamma, a_q, inner, head_block,
                  interpret):
    *out, entries = _scan_fwd(state, w, u_v, q_g, k_d, gamma, a_q, True,
                              inner, head_block,
                              _resolve_interpret(interpret))
    return tuple(out), (entries, w, u_v, q_g, k_d, gamma, a_q)


def _scan_vjp_bwd(inner, head_block, interpret, residuals, cts):
    d_state, d_o = cts
    return tuple(_scan_bwd(*residuals, d_o, d_state, inner, head_block,
                           _resolve_interpret(interpret)))


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def recurrence(state, w, u_v, q_g, k_d, gamma, a_q, dtype,
               interpret: Optional[bool] = None,
               inner: Optional[int] = None,
               head_block: Optional[int] = None):
    """``_recurrence`` of ``parallel/linear_attention.py`` as a kernel pair,
    over operands as ``ops/kda_chunk.py`` leaves them, a token a [heads, d]
    tile: the scan over the n chunks from ``state`` [b, h, d_k, d_v]
    float32. ``w``, ``q_g``, ``k_d`` [b, n, C, h, d_k] and ``a_q`` [b, n, C,
    h, C] (matmul operands: rounded to ``dtype`` here if they are not),
    ``u_v`` [b, n, C, h, d_v] and ``gamma`` [b, n, h, d_k] (or [b, n, h, 1],
    one decay a head) float32. Returns (the state after the last chunk, o
    [b, n, C, h, d_v] float32). ``interpret`` as ``flash_attention`` takes
    it; ``inner`` (a divisor of n; by default the largest up to ``_INNER``)
    chunks an inner group and ``head_block`` heads a grid step (a multiple
    of 8, or all; by default what keeps an inner group's states in VMEM) are
    the tests' handles."""
    w, q_g, k_d, a_q = (x.astype(dtype) for x in (w, q_g, k_d, a_q))
    gamma = jnp.broadcast_to(gamma, k_d.shape[:2] + k_d.shape[3:])
    return _scan(state, w, u_v, q_g, k_d, gamma, a_q, inner, head_block,
                 interpret)
