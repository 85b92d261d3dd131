"""Pallas TPU kernels for the output chain of a Mamba-2 mixer
(``models/nemotron_h.py::gated_group_norm``): the skip, the SiLU gate and
the group norm that stand between the scan and the out-projection,

    h = (y + D x) silu(z);     out = h rsqrt(mean_group h^2 + eps) w,

gate before norm, the mean over each group of channels, float32 arithmetic
and one rounding to ``dtype`` (bf16) at the end: the operand the
out-projection's matmul takes. As XLA runs it the chain is a producer fused
into that matmul and computed again for every column tile of the product,
and its backward pass several passes over float32 ``[s, inner]`` tensors.

One grid step is a block ``[rows, group width]`` of one sequence, tokens on
sublanes and ONE group's channels on lanes, as the scan leaves ``y`` and
``x``: the group's mean of squares is a lane reduction inside the block and
nothing is laid out again. ``z`` is read where it lies, the first ``inner``
columns of the in-projection's wider bf16 output (a block's column index: no
slice is written), and widened in VMEM; ``x`` may lie so too, in the
convolution's ``[x | B | C]``. The forward writes the bf16 operand
alone: 4 + 4 + 2 bytes an element read, 2 written.

The backward kernel keeps nothing of the forward but its inputs. From the
cotangent ``c`` of ``out`` it rebuilds ``h`` and ``r = rsqrt(..)`` in VMEM,

    n = h r       dn = c w       dh = r (dn - n mean_group(dn n))
    dy = dh silu(z)     dx = D dy     dz = dh (y + D x) silu'(z)

and writes ``dy`` and ``dx`` (float32: the scan's backward kernel takes
both as operands), ``dz`` (z's dtype) and a block's sums over its rows of
``c n`` and ``dy x``, ``[blocks, inner]`` float32, which XLA sums into ``dw``
and a channel's share of ``dD``.

Both kernels walk a block ``_PIECE`` rows at a time, straight-line, so that
a piece goes from its loads to its stores in registers
(``ops/causal_conv.py`` has the measurement). The ``pallas_call``s are named
``bps_gated_norm_fwd`` / ``bps_gated_norm_bwd``. Off-TPU they run in
interpret mode, so the CPU tests run this code. They ask for no more than
the scoped VMEM a kernel gets unasked: at most ``MAX_GROUP_WIDTH`` lanes a
block.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from byteps_tpu.ops.flash_attention import _resolve_interpret, pl, pltpu

FWD_NAME, BWD_NAME = "bps_gated_norm_fwd", "bps_gated_norm_bwd"

ROWS = 512              # tokens a block
LANES = 128
MAX_GROUP_WIDTH = 512   # a group's channels: the lanes of a block
_TILE = 16              # rows of a bf16 tile: blocks and pieces in these
_PIECE = 32             # rows of a block held in registers at a time
_VMEM = pltpu.VMEM
F32 = jnp.float32


def _pieces(rows: int):
    """``(start, rows)`` of the pieces a kernel walks a block in."""
    return [(start, min(_PIECE, rows - start))
            for start in range(0, rows, _PIECE)]


def _gated(y, x, z, skip):
    """``(h, u, sigmoid(z), silu(z))`` of a piece, float32."""
    z = z.astype(F32)
    u = y.astype(F32) + skip * x.astype(F32)
    sig = jax.nn.sigmoid(z)
    gate = z * sig
    return u * gate, u, sig, gate


def _by_sublane(piece):
    """[rows, lanes] -> [8, lanes]: the sum of its tiles of 8 rows, on the
    VPU."""
    return sum(piece[i:i + 8] for i in range(0, piece.shape[0], 8))


def _fwd_kernel(y_ref, x_ref, z_ref, skip_ref, w_ref, out_ref, *, rows: int,
                eps: float):
    skip, w = skip_ref[...], w_ref[...]
    for start, n in _pieces(rows):
        at = slice(start, start + n)
        h = _gated(y_ref[at, :], x_ref[at, :], z_ref[at, :], skip)[0]
        r = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
        out_ref[at, :] = (h * r * w).astype(out_ref.dtype)


def _bwd_kernel(y_ref, x_ref, z_ref, skip_ref, w_ref, ct_ref, dy_ref, dx_ref,
                dz_ref, dskip_ref, dw_ref, *, rows: int, eps: float):
    skip, w = skip_ref[...], w_ref[...]
    # a block's sums over its rows, the eight sublanes apart until the end
    sum_w = sum_skip = None
    for start, n in _pieces(rows):
        at = slice(start, start + n)
        x = x_ref[at, :].astype(F32)
        z = z_ref[at, :].astype(F32)
        h, u, sig, gate = _gated(y_ref[at, :], x, z, skip)
        r = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
        normed = h * r
        ct = ct_ref[at, :].astype(F32)
        dn = ct * w
        dh = r * (dn - normed * jnp.mean(dn * normed, axis=-1,
                                         keepdims=True))
        dy = dh * gate
        dy_ref[at, :] = dy.astype(dy_ref.dtype)
        dx_ref[at, :] = (skip * dy).astype(dx_ref.dtype)
        dz_ref[at, :] = (dh * u * (sig * (1.0 + z * (1.0 - sig)))).astype(
            dz_ref.dtype)
        sum_w, sum_skip = (
            part if acc is None else acc + part for acc, part in zip(
                (sum_w, sum_skip), map(_by_sublane, (ct * normed, dy * x))))
    dw_ref[...] = jnp.sum(sum_w, axis=0, keepdims=True)
    dskip_ref[...] = jnp.sum(sum_skip, axis=0, keepdims=True)


def _sizes(y, x, z, skip, weight, groups: int, rows: int):
    """``(b, s, inner, width)`` of a call the tiling holds, else
    ValueError."""
    if y.ndim != 3 or x.ndim != 3 or z.ndim != 3:
        raise ValueError(f"gated_norm kernel: y [b, s, inner], x, z [b, s, "
                         f">= inner]; got {y.shape}, {x.shape}, {z.shape}")
    b, s, inner = y.shape
    if any(t.shape[:2] != (b, s) or t.shape[2] < inner for t in (x, z)):
        raise ValueError(f"gated_norm kernel: x, z [{b}, {s}, >= {inner}]; "
                         f"got {x.shape}, {z.shape}")
    if skip.shape != (inner,) or weight.shape != (inner,):
        raise ValueError(f"gated_norm kernel: skip, weight [{inner}]; got "
                         f"{skip.shape}, {weight.shape}")
    width = inner // groups if groups >= 1 and inner % groups == 0 else 0
    if (width == 0 or width % LANES or width > MAX_GROUP_WIDTH
            or s % rows or rows % _TILE):
        raise ValueError(
            f"gated_norm kernel: {groups} groups of inner ({inner}) in "
            f"{LANES}s of lanes up to {MAX_GROUP_WIDTH}, the sequence ({s}) "
            f"in blocks of {rows} rows, a block in {_TILE}s")
    return b, s, inner, width


def _specs(width: int, rows: int):
    """Over the grid (batch, row block, group): a ``[b, s, >= inner]``
    operand's block, a ``[1, inner]`` operand's, a ``[b, blocks, 1, inner]``
    result's."""
    block = pl.BlockSpec((None, rows, width), lambda b, r, j: (b, r, j),
                         memory_space=_VMEM)
    channels = pl.BlockSpec((1, width), lambda b, r, j: (0, j),
                            memory_space=_VMEM)
    sums = pl.BlockSpec((None, None, 1, width), lambda b, r, j: (b, r, 0, j),
                        memory_space=_VMEM)
    return block, channels, sums


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel"))


@functools.partial(jax.jit, static_argnames=("groups", "eps", "dtype",
                                             "rows", "interpret"))
def _forward(y, x, z, skip, weight, groups, eps, dtype, rows, interpret):
    b, s, inner, width = _sizes(y, x, z, skip, weight, groups, rows)
    block, channels, _ = _specs(width, rows)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, rows=rows, eps=eps),
        grid=(b, s // rows, groups),
        in_specs=[block, block, block, channels, channels], out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, inner), dtype),
        compiler_params=_params(), interpret=interpret, name=FWD_NAME)(
            y, x, z, skip.astype(F32)[None], weight.astype(F32)[None])


@functools.partial(jax.jit, static_argnames=("groups", "eps", "rows",
                                             "interpret"))
def _backward(y, x, z, skip, weight, ct, groups, eps, rows, interpret):
    b, s, inner, width = _sizes(y, x, z, skip, weight, groups, rows)
    if ct.shape != y.shape:
        raise ValueError(f"gated_norm kernel: a cotangent {y.shape}; got "
                         f"{ct.shape}")
    block, channels, sums = _specs(width, rows)
    sums_shape = jax.ShapeDtypeStruct((b, s // rows, 1, inner), F32)
    dy, dx, dz, dskip, dw = pl.pallas_call(
        functools.partial(_bwd_kernel, rows=rows, eps=eps),
        grid=(b, s // rows, groups),
        in_specs=[block, block, block, channels, channels, block],
        out_specs=[block, block, block, sums, sums],
        out_shape=[jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct(y.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, z.dtype),
                   sums_shape, sums_shape],
        compiler_params=_params(), interpret=interpret, name=BWD_NAME)(
            y, x, z, skip.astype(F32)[None], weight.astype(F32)[None], ct)
    return dy, dx, dz, dskip.sum((0, 1, 2)), dw.sum((0, 1, 2))


def gated_norm_forward(y, x, z, skip, weight, *, groups: int,
                       eps: float = 1e-5, dtype=jnp.bfloat16,
                       rows: int = ROWS, interpret: Optional[bool] = None):
    """``GN((y + skip x) silu(z)) weight`` [b, s, inner] in ``dtype`` by the
    kernel; no backward rule of its own. y [b, s, inner], x and z [b, s, >=
    inner] (the first ``inner`` columns of each are read) in any float dtype;
    skip and weight [inner], a number a channel; ``groups`` groups of ``inner
    / groups`` channels, whole 128-lane tiles up to ``MAX_GROUP_WIDTH``; s a
    multiple of ``rows`` (a multiple of 16). ``interpret`` as
    ``flash_attention`` takes it."""
    return _forward(y, x, z, skip, weight, groups, float(eps),
                    jnp.dtype(dtype), rows, _resolve_interpret(interpret))


def gated_norm_backward(y, x, z, skip, weight, ct, *, groups: int,
                        eps: float = 1e-5, rows: int = ROWS,
                        interpret: Optional[bool] = None):
    """``(dy, dx, dz, dskip, dweight)`` of ``gated_norm_forward`` under the
    cotangent ``ct`` [b, s, inner] of its output, by the kernel: ``dy``,
    ``dx``, ``dz`` [b, s, inner] (the columns that were read) in the dtypes
    of y, x, z, ``dskip`` and ``dweight`` [inner] float32."""
    return _backward(y, x, z, skip, weight, ct, groups, float(eps), rows,
                     _resolve_interpret(interpret))
