"""Pallas TPU kernels for the chunked state-space scan with no delta rule
(``byteps_tpu.parallel.linear_attention.ssd_scan``, whose arithmetic this is:
Mamba-2's SSD). Per head a float32 state ``S`` [n, p] from zero, ``u = dt
x`` the write, ``G`` the log-decay cumulated inside a chunk of ``L`` tokens
and ``T = G_L`` a chunk's whole decay:

    Y   = (C B^T . e^{G_i - G_j} [j <= i]) U  +  e^{G} . (C S)
    S  <- e^{T} S + B^T (e^{T - G} . U).

As XLA runs it the masks ``e^{G_i - G_j}`` are a float32 ``[heads, L, L]``
tensor a chunk, written to HBM and read back, forward, recomputed and
backward; the state goes through HBM around every chunk; and ``x`` arrives
``[b, s, heads, p]`` with p = 64 lanes, which the convolution before the
scan and the gate after it do not use: both have a token's channels on
lanes, ``[s, channels]``, and pay a relayout each way.

Here the operands are read where the convolution leaves them. One array
``mixed`` [b, s, heads p + 2 groups n] holds ``x`` (head h on lanes ``p h ..
p h + p - 1``), then ``B``, then ``C`` (group j on lanes ``n j .. n j + n -
1`` of each); the grid is (batch, chunks, groups), both inner axes
sequential, and a grid step takes the group's heads of ``x`` as one ``[L,
heads p / groups]`` block of ``mixed``, ``B`` and ``C`` as ``[L, n]`` blocks
of the same array, and writes ``y`` ``[L, heads p / groups]`` into ``[b, s,
heads p]``. Every group's state ``[n, heads p / groups]`` float32 stays in
one VMEM scratch from the first chunk to the last; a chunk's masks are
formed in VMEM, a ``[L, L]`` tile a head, and never leave it.

The products whose left side the group shares are one product each: ``C
B^T`` once a group, the read of the carried state ``C [S_1 .. S_r]`` and
every head's share of the next state ``B^T [(U e^{T - G})_1 .. _r]``. Only
the masked pairs are a product a head, and those go two heads at a time:
with p = 64 a head is half a row of lanes, so ``[M_h | M_h'] [[U_h, 0], [0,
U_h']]`` leaves both heads' ``y`` in one lane-dense ``[L, 128]`` tile at the
MXU time the two half-empty products would take, and nothing is shifted
across lanes.

The log-decay and the step come in two layouts, both 4 MB a layer and made
by XLA: ``G`` and ``dt`` with tokens on sublanes ``[b, s, heads]`` (what
scales a token's row) and ``G`` with tokens on lanes ``[b, heads, s]`` (the
``j`` of ``G_i - G_j``). A head's column is picked out of the ``[L, heads]``
block by a compare against the lane's number, since the group is the grid's.

Same arithmetic as the XLA form: operands of every product rounded to
``dtype`` (bf16), float32 accumulation, the carried state float32 and never
rounded, ``G_i - G_j`` masked to ``-inf`` above the diagonal before the
``exp``, nothing clamped.

**The backward pass** is hand-written (``custom_vjp``) and keeps nothing
of the forward but its inputs. It first walks the chunks forward again with
the forward kernel's state update alone (``states``: no pairs, no ``y``),
leaving every chunk's first state ``[chunks, groups, n, heads p / groups]``
float32 in HBM (268 MB a layer at the Nemotron cell's shapes, alive for the
backward rule only), then walks them from the last to the first with ``dS``
in VMEM:

    dM_h = dY_h U_h^T          dCB = sum_h dM_h . D_h       X_h = dM_h . M_h
    dU_h = M_h^T dY_h + e^{T - G} . (B dS'_h)
    dC = dCB B + (e^G . dY) S^T          dB = dCB^T C + (e^{T - G} . U) dS'^T
    dS = e^T dS' + C^T (e^G . dY)
    dG_i += rowsum X_h + rowsum(dY . e^G . C S) ;  dG_j -= colsum X_h + ...

with the sums of ``dG`` taken in float32 on the VPU (they cancel against
each other under the cumulated sum's transpose; a product on the MXU would
round them). ``dx``, ``dB``, ``dC`` come back in the layout ``mixed`` has,
and the op hands ``x`` back beside ``y`` so that what a caller's use of it
sends back is added to ``dx`` inside the kernel (``ssd_scan_kernel``).

The ``pallas_call``s are named ``bps_ssd_scan_fwd`` / ``bps_ssd_scan_states``
/ ``bps_ssd_scan_bwd``. Off-TPU they run in interpret mode, so the CPU tests
run this code.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.ops.flash_attention import _resolve_interpret, pl, pltpu

FWD_NAME, STATES_NAME, BWD_NAME = (
    "bps_ssd_scan_fwd", "bps_ssd_scan_states", "bps_ssd_scan_bwd")

LANES = 128         # a row of lanes: the state's entries, the chunk, a pair
_VMEM = pltpu.VMEM
_VMEM_LIMIT = 64 * 1024 * 1024
F32 = jnp.float32

# 2-D contractions: plain, against a transposed right-hand side, over the
# rows of both
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(x, y, contract):
    return lax.dot_general(x, y, (contract, ((), ())),
                           preferred_element_type=F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


class _Group:
    """What the three kernels share of a grid step: the group's ``B`` and
    ``C`` in ``dtype``, ``C B^T``, and head by head the columns, masks and
    decays that the chunk's ``G`` and ``dt`` give."""

    def __init__(self, b_ref, c_ref, gcol_ref, grow_ref, dt_ref, group,
                 dtype, pairs: bool):
        size = b_ref.shape[0]
        self.b, self.c = b_ref[...].astype(dtype), c_ref[...].astype(dtype)
        self.cb = _dot(self.c, self.b, _NT) if pairs else None   # [i, j]
        self._gcol, self._dt = gcol_ref[...], dt_ref[...]
        self._grow_ref = grow_ref
        self._lane = _iota(self._gcol.shape, 1)
        self._first = group * grow_ref.shape[0]
        self.lo = _iota((size, LANES), 1) < LANES // 2
        self.lo_row = self.lo[:1]
        self.lower = _iota((size, size), 0) >= _iota((size, size), 1)
        self.last = _iota((size, 1), 0) == size - 1

    def column(self, values, h):
        """Head ``h`` of the group out of ``values`` [L, heads of all
        groups]: [L, 1]. One lane is not masked away, so the sum is exact."""
        return jnp.sum(jnp.where(self._lane == self._first + h, values, 0.0),
                       axis=1, keepdims=True)

    def g_col(self, h):
        return self.column(self._gcol, h)

    def dt_col(self, h):
        return self.column(self._dt, h)

    def total(self, g_col):
        """``T``: the chunk's last cumulated log-decay, [1, 1]."""
        return jnp.sum(jnp.where(self.last, g_col, 0.0), axis=0,
                       keepdims=True)

    def decay(self, g_col, h):
        """``D_h`` [i, j] = e^{G_i - G_j} for j <= i, 0 above the diagonal:
        masked to ``-inf`` before the ``exp``, never clamped."""
        return jnp.exp(jnp.where(
            self.lower, g_col - self._grow_ref[h:h + 1, :], -jnp.inf))

    def scatter(self, columns):
        """[L, heads of all groups]: ``columns[h]`` [L, 1] on head h of the
        group's lane, zeros elsewhere."""
        out = jnp.zeros(self._gcol.shape, F32)
        for h, col in enumerate(columns):
            out = out + jnp.where(self._lane == self._first + h, col, 0.0)
        return out


def _pair_writes(group: _Group, x_pair, k: int):
    """Of heads 2k and 2k + 1 of the group, the first on a tile's lower 64
    lanes and the second on its upper: their ``G`` columns, ``U`` [L, 128]
    float32, and ``dt``, ``e^G``, ``e^{T - G}`` tiles [L, 128] and ``e^T``
    [1, 128]."""
    cols = [group.g_col(2 * k + t) for t in (0, 1)]
    totals = [group.total(col) for col in cols]
    dt = jnp.where(group.lo, *(group.dt_col(2 * k + t) for t in (0, 1)))
    gate = jnp.exp(jnp.where(group.lo, *cols))
    left = jnp.exp(jnp.where(group.lo, *(tot - col for tot, col in
                                         zip(totals, cols))))
    gamma = jnp.exp(jnp.where(group.lo_row, *totals))
    return cols, x_pair * dt, dt, gate, left, gamma


def _split(lo, tile):
    """``[[tile's first head, 0], [0, its second head]]`` [2 L, 128]."""
    zero = jnp.zeros_like(tile)
    return jnp.concatenate([jnp.where(lo, tile, zero),
                            jnp.where(lo, zero, tile)], axis=0)


def _fwd_kernel(x_ref, b_ref, c_ref, gcol_ref, grow_ref, dt_ref, out_ref,
                state_ref, *, emit_y: bool, dtype):
    n, j = pl.program_id(1), pl.program_id(2)
    group = _Group(b_ref, c_ref, gcol_ref, grow_ref, dt_ref, j, dtype,
                   pairs=emit_y)

    @pl.when(n == 0)
    def _():
        state_ref[j] = jnp.zeros(state_ref.shape[1:], F32)

    state = state_ref[j]                             # [n, r p] float32
    if emit_y:
        read = _dot(group.c, state.astype(dtype), _NN)      # C S
    else:
        out_ref[...] = state                 # the chunk's first state
    writes, gammas = [], []
    for k in range(x_ref.shape[1] // LANES):
        at = slice(k * LANES, (k + 1) * LANES)
        cols, u, _, gate, left, gamma = _pair_writes(group, x_ref[:, at], k)
        if emit_y:
            masked = jnp.concatenate(
                [(group.cb * group.decay(col, 2 * k + t)).astype(dtype)
                 for t, col in enumerate(cols)], axis=1)     # [L, 2 L]
            out_ref[:, at] = (
                _dot(masked, _split(group.lo, u.astype(dtype)), _NN)
                + gate * read[:, at])
        writes.append((u * left).astype(dtype))
        gammas.append(gamma)
    state_ref[j] = (jnp.concatenate(gammas, axis=1) * state
                    + _dot(group.b, jnp.concatenate(writes, axis=1), _TN))


def _bwd_kernel(x_ref, b_ref, c_ref, gcol_ref, grow_ref, dt_ref, dy_ref,
                dxv_ref, s_ref, dx_ref, db_ref, dc_ref, dgcol_ref,
                dgrow_ref, ddt_ref, dstate_ref, *, dtype):
    n, j = pl.program_id(1), pl.program_id(2)
    group = _Group(b_ref, c_ref, gcol_ref, grow_ref, dt_ref, j, dtype,
                   pairs=True)
    lo = group.lo

    @pl.when(n == 0)
    def _():
        dstate_ref[j] = jnp.zeros(dstate_ref.shape[1:], F32)

    @pl.when(j == 0)
    def _():        # the chunk's [L, heads] blocks: every group adds its own
        dgcol_ref[...] = jnp.zeros(dgcol_ref.shape, F32)
        ddt_ref[...] = jnp.zeros(ddt_ref.shape, F32)

    state, dnext = s_ref[...], dstate_ref[j]         # S, dS' float32
    state_, dnext_ = state.astype(dtype), dnext.astype(dtype)
    read = _dot(group.c, state_, _NN)                # C S      [i, r p]
    back = _dot(group.b, dnext_, _NN)                # B dS'    [j, r p]

    def by_head(tile):
        """[L, 128] -> the two heads' sums over their lanes, [L, 1] each."""
        zero = jnp.zeros_like(tile)
        return [jnp.sum(jnp.where(lo, tile, zero), axis=1, keepdims=True),
                jnp.sum(jnp.where(lo, zero, tile), axis=1, keepdims=True)]

    dcb = jnp.zeros(group.cb.shape, F32)
    gated, writes, gammas = [], [], []
    dg_cols, dg_rows, ddt_cols = [], [], []
    for k in range(x_ref.shape[1] // LANES):
        at = slice(k * LANES, (k + 1) * LANES)
        x = x_ref[:, at]
        cols, u, dt, gate, left, gamma = _pair_writes(group, x, k)
        u_ = u.astype(dtype)
        dy = dy_ref[:, at]
        dy_halves = _split(lo, dy.astype(dtype))             # [2 L, 128]
        size = dy.shape[0]
        masked, row_sums = [], []
        for t, col in enumerate(cols):
            decay = group.decay(col, 2 * k + t)
            m = group.cb * decay
            dm = _dot(dy_halves[t * size:(t + 1) * size], u_, _NT)
            dcb = dcb + dm * decay
            both = dm * m                            # dD . D, float32
            row_sums.append(jnp.sum(both, axis=1, keepdims=True))
            dg_rows.append(-jnp.sum(both, axis=0, keepdims=True))
            masked.append(m.astype(dtype))
        # dU = M^T dY + e^{T - G} . (B dS')
        du = (_dot(jnp.concatenate(masked, axis=0), dy_halves, _TN)
              + left * back[:, at])
        dx_ref[:, at] = dt * du + dxv_ref[:, at]
        ddt_cols += by_head(du * x)
        # through e^G of the read, e^{T - G} of the write and e^T
        reads = by_head(dy * gate * read[:, at])
        wrote = by_head(left * u * back[:, at])
        kept = by_head(jnp.sum(gamma * dnext[:, at] * state[:, at], axis=0,
                               keepdims=True))
        for t in (0, 1):
            d_total = jnp.sum(wrote[t], axis=0, keepdims=True) + kept[t]
            dg_cols.append(row_sums[t] + reads[t] - wrote[t]
                           + jnp.where(group.last, d_total, 0.0))
        gated.append((gate * dy).astype(dtype))
        writes.append((u * left).astype(dtype))
        gammas.append(gamma)
    gated, writes = (jnp.concatenate(x, axis=1) for x in (gated, writes))
    dcb_ = dcb.astype(dtype)
    dc_ref[...] = _dot(dcb_, group.b, _NN) + _dot(gated, state_, _NT)
    db_ref[...] = _dot(dcb_, group.c, _TN) + _dot(writes, dnext_, _NT)
    dstate_ref[j] = (jnp.concatenate(gammas, axis=1) * dnext
                     + _dot(group.c, gated, _TN))
    dgcol_ref[...] += group.scatter(dg_cols)
    ddt_ref[...] += group.scatter(ddt_cols)
    dgrow_ref[...] = jnp.concatenate(dg_rows, axis=0)


def _specs(shape, heads: int, groups: int, state: int, chunk_of):
    """The block specs of a grid step over (batch, chunk ``chunk_of(t)``,
    group): of ``mixed`` [b, s, channels] its ``x``, ``B`` and ``C`` blocks,
    of the [b, s, heads] and [b, heads, s] arrays a chunk's block, and of a
    [b, s, heads p] array the group's block."""
    b, s, channels = shape
    inner = channels - 2 * groups * state
    wide, rows = inner // groups, heads // groups
    first_b = inner // state

    def spec(block, index):
        return pl.BlockSpec((None,) + block, index, memory_space=_VMEM)

    x = spec((LANES, wide), lambda i, t, j: (i, chunk_of(t), j))
    b_ = spec((LANES, state), lambda i, t, j: (i, chunk_of(t), first_b + j))
    c_ = spec((LANES, state),
              lambda i, t, j: (i, chunk_of(t), first_b + groups + j))
    col = spec((LANES, heads), lambda i, t, j: (i, chunk_of(t), 0))
    row = spec((rows, LANES), lambda i, t, j: (i, j, chunk_of(t)))
    small = spec((LANES, state), lambda i, t, j: (i, chunk_of(t), j))
    return x, b_, c_, col, row, small


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _sizes(mixed, g_rows, groups: int, state: int):
    b, s, channels = mixed.shape
    heads = g_rows.shape[1]
    inner = channels - 2 * groups * state
    if (state != LANES or s % LANES or heads % groups
            or inner % (groups * LANES) or inner // heads != LANES // 2
            or (heads // groups) % 8):
        raise ValueError(
            "ssd_scan kernel: a state of 128 entries, heads of 64 channels "
            "in eights a group, the sequence in chunks of 128; got "
            f"mixed {mixed.shape}, {heads} heads, {groups} groups of "
            f"{state}")
    return b, s, heads, inner


@functools.partial(jax.jit, static_argnames=("groups", "state", "emit_y",
                                             "dtype", "interpret"))
def _forward(mixed, g_cols, g_rows, dt, groups, state, emit_y, dtype,
             interpret):
    """``y`` [b, s, heads p] if ``emit_y``, else every chunk's first state
    [b, chunks, groups, n, heads p / groups] float32."""
    b, s, heads, inner = _sizes(mixed, g_rows, groups, state)
    n, wide = s // LANES, inner // groups
    x, b_, c_, col, row, _ = _specs(mixed.shape, heads, groups, state,
                                    lambda t: t)
    if emit_y:
        out_shape, out_spec = jax.ShapeDtypeStruct((b, s, inner), F32), x
    else:
        out_shape = jax.ShapeDtypeStruct((b, n, groups, state, wide), F32)
        out_spec = pl.BlockSpec((None, None, None, state, wide),
                                lambda i, t, j: (i, t, j, 0, 0),
                                memory_space=_VMEM)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, emit_y=emit_y, dtype=dtype),
        grid=(b, n, groups), in_specs=[x, b_, c_, col, row, col],
        out_specs=out_spec, out_shape=out_shape,
        scratch_shapes=[_VMEM((groups, state, wide), F32)],
        compiler_params=_params(), interpret=interpret,
        name=FWD_NAME if emit_y else STATES_NAME)(
            mixed, mixed, mixed, g_cols, g_rows, dt)


@functools.partial(jax.jit, static_argnames=("groups", "state", "dtype",
                                             "interpret"))
def _backward(mixed, g_cols, g_rows, dt, dy, dxv, states, groups, state,
              dtype, interpret):
    b, s, heads, inner = _sizes(mixed, g_rows, groups, state)
    n, wide = s // LANES, inner // groups

    def back(t):        # the chunks from the last to the first
        return n - 1 - t

    x, b_, c_, col, row, small = _specs(mixed.shape, heads, groups, state,
                                        back)
    states_spec = pl.BlockSpec((None, None, None, state, wide),
                               lambda i, t, j: (i, back(t), j, 0, 0),
                               memory_space=_VMEM)
    # ``dx`` goes where it stands in ``mixed``'s cotangent: the call leaves
    # the channels of ``B`` and ``C`` there unwritten, and the caller puts
    # ``dB`` and ``dC`` over them in place
    wide_out = jax.ShapeDtypeStruct(mixed.shape, F32)
    small_out = jax.ShapeDtypeStruct((b, s, groups * state), F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, dtype=dtype), grid=(b, n, groups),
        in_specs=[x, b_, c_, col, row, col, x, x, states_spec],
        out_specs=[x, small, small, col, row, col],
        out_shape=[wide_out, small_out, small_out,
                   jax.ShapeDtypeStruct(g_cols.shape, F32),
                   jax.ShapeDtypeStruct(g_rows.shape, F32),
                   jax.ShapeDtypeStruct(dt.shape, F32)],
        scratch_shapes=[_VMEM((groups, state, wide), F32)],
        compiler_params=_params(), interpret=interpret, name=BWD_NAME)(
            mixed, mixed, mixed, g_cols, g_rows, dt, dy, dxv, states)


def _x_of(mixed, groups, state):
    return mixed[..., :mixed.shape[-1] - 2 * groups * state]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _scan(mixed, g_cols, g_rows, dt, groups, state, dtype, interpret):
    return (_forward(mixed, g_cols, g_rows, dt, groups, state, True, dtype,
                     _resolve_interpret(interpret)),
            _x_of(mixed, groups, state))


def _scan_vjp_fwd(mixed, g_cols, g_rows, dt, groups, state, dtype,
                  interpret):
    return (_scan(mixed, g_cols, g_rows, dt, groups, state, dtype,
                  interpret), (mixed, g_cols, g_rows, dt))


def _scan_vjp_bwd(groups, state, dtype, interpret, residuals, cts):
    interpret = _resolve_interpret(interpret)
    # every chunk's first state again: the forward kept none (kept, they
    # would be recomputed with ``y`` under a mixer's remat: PERF.md section
    # 6, PR 65)
    states = _forward(*residuals, groups, state, False, dtype, interpret)
    dx, db, dc, dg_cols, dg_rows, ddt = _backward(
        *residuals, *cts, states, groups, state, dtype, interpret)
    inner = dx.shape[-1] - 2 * db.shape[-1]
    dmixed = lax.dynamic_update_slice_in_dim(dx, db, inner, axis=2)
    dmixed = lax.dynamic_update_slice_in_dim(dmixed, dc,
                                             inner + db.shape[-1], axis=2)
    return dmixed, dg_cols, dg_rows, ddt


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def ssd_scan_kernel(mixed, log_decay, dt, *, groups: int, state: int,
                    dtype=jnp.bfloat16, interpret: Optional[bool] = None):
    """``(y, x)``: ``y`` [b, s, heads p] float32 of the state-space
    recurrence over ``mixed`` [b, s, heads p + 2 groups n] float32 = ``[x |
    B | C]`` as the convolution leaves them, under ``log_decay`` [b, s,
    heads] float32, the log-decay *cumulated inside each chunk of 128
    tokens*, and the step ``dt`` [b, s, heads] float32; and ``x`` itself,
    ``mixed``'s first heads p channels, for a caller that uses it beside
    ``y`` (a mixer's skip ``D x``): its cotangent then comes through this
    op, and the backward kernel adds it to its own ``dx`` where that stands
    in ``mixed``'s cotangent, where a slice of ``mixed`` taken by the caller
    has XLA pad it with zeros to ``mixed``'s width and add the two ``[s,
    channels]`` tensors (1.6 ms a layer at the Nemotron cell's shapes:
    PERF.md section 6, PR 65). n = 128, p = 64, the heads of a group a
    multiple of 8, s a multiple of 128 (``parallel/linear_attention.py::
    ssd_form`` has the rule). Differentiable in all three. ``interpret`` as
    ``flash_attention`` takes it."""
    return _scan(mixed, log_decay, jnp.swapaxes(log_decay, 1, 2), dt, groups,
                 state, jnp.dtype(dtype), interpret)
