"""Pallas TPU flash attention (forward kernel + training VJP).

Blockwise attention with online softmax: Q blocks in VMEM, the kernel
streams K/V blocks and keeps only O(block) state — never materialising the
[S, S] score matrix in HBM. Block matmuls hit the MXU at the (128, 128)
tile shape; masking (causal / key padding) is computed on the VPU with
broadcasted iota, on the blocks a mask can change. Per
/opt/skills/guides/pallas_guide.md patterns: grid iterates (batch*heads,
q_block, k_block) with the k_block dimension innermost so VMEM scratch
carries the running (m, l, acc) across K steps.

Layout as byteps_tpu.parallel attention: [batch, seq, heads, head_dim], v's
head_dim (and the output's) free to differ from q's and k's; f32 accumulation.

The backward pass is the standard flash-attention blockwise recompute from
the forward's saved (q, k, v, o, logsumexp) — O(seq) memory end to end — in
one of two forms that ``backward_form`` picks from the shapes: one kernel
that computes a block's p and dS once and adds dV, dK and dQ from them
(five products a block; dK and dV summed in float32 in VMEM over the whole
sequence), or, where those sums do not fit, the pair it replaced (dQ, and
dK/dV: seven products, the block recomputed in each). Off-TPU the kernels
run in interpret mode, so tests exercise the real kernel code paths on CPU.

Both matmul operands of every product are in the operands' own dtype (bf16
on the hot path), logits, running max / sum and every accumulator float32.
Causal blocks wholly above the diagonal are neither computed nor fetched
(their index maps repeat the last live block, so the pipeline issues no
copy). The ``pallas_call``s are named ``bps_flash_fwd`` and
``bps_flash_bwd`` (the pair: ``bps_flash_dq`` / ``bps_flash_dkv``), forward
and backward each under a ``jax.jit`` of its own, so a model with N
attention layers of one shape traces and lowers them once; what a process
pays before its first step for using them is in
PERF.md section 6 (PR 36): the import, and 0.2-0.6 s of tracing a program.
``byteps_tpu.parallel.full_attention`` hands this kernel the shapes where
it beats XLA's form on the chip (PERF.md section 3, kernels).

``k`` and ``v`` may have fewer heads than ``q`` (a divisor): the K/V index
maps read head ``i // groups`` and dK/dV sum their group in the kernel (the
fused kernel over the group's consecutive rows of its grid, the pair's
dK/dV kernel walking the group's heads one after another over one key
block), so no repeated K/V and no [heads] dK/dV are written (at 64 / 8 x
128 x s8192 3-9% faster than repeating ahead of the call, PERF.md section
3). Under a ``window`` every grid walks only the blocks the band touches.
Of the blocks a grid computes (the live ones), one that no mask can change
— wholly under the diagonal, inside the window, no padding: ``_interior``,
from the block's corners — runs the same arithmetic with no mask formed,
in all four kernels; the diagonal's blocks, a band's edges and a padded
tail run the masked body (``block_census`` counts both kinds).
"""

from __future__ import annotations

import functools
import sys
from typing import Optional

import jax
import jax.numpy as jnp


def _import_pallas():
    """``jax.experimental.pallas`` and its ``tpu`` module, without the Mosaic
    GPU interpreter. Every process that reaches the kernel pays this import
    before its first step, from source (the image caches no bytecode): 1.14
    s on the chip's host, 0.86 of them the GPU interpreter's LLVM and NVVM
    dialects (my chip runs, PR 36), which ``jax/_src/pallas/pallas_call.py``
    imports under ``except ImportError`` and a TPU kernel never touches. A
    ``None`` in ``sys.modules`` is Python's own way to say a module is
    absent; it is taken out again, so a later import of it finds it. If
    Pallas is loaded already, or will not load this way, the plain import."""
    gpu = "jax._src.pallas.mosaic_gpu.interpret"
    halted = (gpu not in sys.modules
              and "jax.experimental.pallas" not in sys.modules)
    if halted:
        sys.modules[gpu] = None
    try:
        from jax.experimental import pallas
        from jax.experimental.pallas import tpu
    except ImportError:
        if not halted:
            raise
        del sys.modules[gpu]
        halted = False
        from jax.experimental import pallas
        from jax.experimental.pallas import tpu
    finally:
        if halted:
            del sys.modules[gpu]
    return pallas, tpu


pl, pltpu = _import_pallas()
_VMEM = pltpu.VMEM

_NEG_INF = -1e30

# The largest block under a window shorter than 1024 keys (``_blocks``): such
# a band crosses few blocks, and a block computes all its pairs.
WINDOW_BLOCK = 512

# The kernels' names: what a device trace and the ledger's ``device_ops``
# show for the custom calls (forward and fused backward, or the pair).
FWD_NAME, BWD_NAME = "bps_flash_fwd", "bps_flash_bwd"
DQ_NAME, DKV_NAME = "bps_flash_dq", "bps_flash_dkv"

# The most the fused backward's call may ask of the chip's 128 MiB of VMEM
# (the ``_VMEM_LIMIT`` of ``ops/kda_chunk.py`` and ``ops/kda_recurrence.py``),
# and what a kernel gets unasked. ``backward_form`` counts what a call of the
# shapes holds there (``_fused_vmem_bytes``) and gives it the fused kernel
# where that is within the first; the call asks for what was counted and no
# more. What a call asks for is felt outside it: the JoyAI cell's
# ``peak_hbm_gb`` read 8.5969 with every call asking 64 MiB, 8.6642 asking 96
# (the parent's 8.5992 + 0.76%, against a bound of 1%) and 8.5965 asking the
# 47 counted for its shape (my chip runs, PR 57).
_FUSED_VMEM_LIMIT = 96 * 1024 * 1024
_VMEM_UNASKED = 16 * 1024 * 1024


def _mask(q_start, k_start, bq, bk, seq_q, seq_k, causal, window):
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < seq_k                   # key padding
    if seq_q is not None:
        mask = jnp.logical_and(mask, q_pos < seq_q)
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window is not None:
            # sliding window: attend to the last `window` positions
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _interior(q_start, k_start, bq, bk, seq_q, seq_k, causal, window):
    """Whether ``_mask`` of the block is all true, from the block's corners:
    it holds no padding (keys; queries where ``seq_q`` is given, as the
    backward kernels give it), its last key is no later than its first
    query, and its farthest pair — last query, first key — is inside the
    window. ``False`` (the Python value) where no block of the call can be:
    a causal block's farthest pair lies ``bq + bk - 2`` apart at the least,
    so a window shorter than ``bq + bk - 1`` has edge blocks alone."""
    if causal and window is not None and window < bq + bk - 1:
        return False
    inside = k_start + bk <= seq_k
    if seq_q is not None:
        inside &= q_start + bq <= seq_q
    if causal:
        inside &= k_start + bk - 1 <= q_start
        if window is not None:
            inside &= q_start + bq - 1 - k_start < window
    return inside


def _when_live(live, interior, compute):
    """``compute(masked)`` unless the block is dead: ``compute(False)``, the
    same arithmetic with no mask formed, where the block is ``interior``
    (which implies live), ``compute(True)`` on the edge blocks. ``live`` is
    ``True`` and ``interior`` ``False`` (the Python values) where no mask
    can kill a whole block and where none can spare one."""
    edge = live
    if interior is not False:
        pl.when(interior)(lambda: compute(False))
        edge = jnp.logical_and(live, jnp.logical_not(interior))
    if edge is True:
        compute(True)
    else:
        pl.when(edge)(lambda: compute(True))


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
               *, scale: float, causal: bool, block_q: int, block_k: int,
               seq_k: int, window: Optional[int] = None,
               nk_total: Optional[int] = None):
    # nk_total set => restricted-window grid: the third grid dim walks only
    # the ~window/block_k live k blocks per q block (see _window_kv_index).
    """One (bh, qi, ki) grid step of blockwise attention."""
    ki = pl.program_id(2)
    qi = pl.program_id(1)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    if nk_total is None:
        k_start = ki * block_k
    else:
        # real (unclamped) k block this step serves; duplicates from the
        # index-map clamp are skipped via the bounds on k_start below
        k_start = (_window_start_block(q_start, window, block_k) + ki) \
            * block_k

    def _compute(masked):
        v = v_ref[0]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        if masked:
            s = jnp.where(_mask(q_start, k_start, block_q, block_k, None,
                                seq_k, causal, window), s, _NEG_INF)

        m_prev = m_ref[:, 0:1]             # [bq, 1]
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
        corr = jnp.exp(m_prev - m_cur)     # [bq, 1]
        # m/l live in 128-lane scratch rows (VMEM tiling); lane 0 is the
        # value, writes broadcast across lanes.
        l_new = l_ref[:, 0:1] * corr + p.sum(axis=-1, keepdims=True)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)
        m_ref[:] = jnp.broadcast_to(m_cur, m_ref.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv

    live = True
    if causal:
        # k_start/q_start are traced (program_id); predicate at runtime.
        live = k_start <= q_start + block_q - 1
        if window is not None:
            # skip blocks entirely left of every query's window
            live = jnp.logical_and(
                live, k_start + block_k - 1 >= q_start - (window - 1))
        if nk_total is not None:
            live = jnp.logical_and(live, k_start < nk_total * block_k)
    _when_live(live, _interior(q_start, k_start, block_q, block_k, None,
                               seq_k, causal, window), _compute)

    @pl.when(ki == nk - 1)
    def _finish():
        # Fully-masked rows (query padding) have l == 0; guard the divide.
        l = l_ref[:, 0:1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        # logsumexp per row (scaled-score space) for the backward pass;
        # +LARGE for empty rows so exp(s - lse) underflows to exactly 0.
        lse = jnp.where(l == 0.0, _NEG_INF * -1.0,
                        m_ref[:, 0:1] + jnp.log(safe_l))
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _window_start_block(q_start, window, block_k):
    """First k block that can be inside [q_start - window + 1, ...]."""
    return jnp.maximum((q_start - (window - 1)) // block_k, 0)


def _window_k_blocks(window: int, block_q: int, block_k: int,
                     nq: int, nk: int) -> list:
    """For each q block, the k blocks its band touches: keys ``q_start -
    window + 1 .. q_start + block_q - 1``, counted block by block (a
    static loop over at most a few dozen q blocks) and not bounded by a
    formula, which at 256 x 512 would walk a third block no query needs.
    The one model of the band's blocks: the grids are sized by its largest
    entry, ``window_walked_pairs`` sums it."""
    return [min((qi * block_q + block_q - 1) // block_k, nk - 1)
            - max(qi * block_q - window + 1, 0) // block_k + 1
            for qi in range(nq)]


def _window_live_blocks(window: int, block_q: int, block_k: int,
                        nq: int, nk: int) -> int:
    """The most k blocks the band of one q block touches: the third
    dimension of the forward's and dQ's grids under a window."""
    return max(_window_k_blocks(window, block_q, block_k, nq, nk))


def _window_live_q_blocks(window: int, block_q: int, block_k: int,
                          nq: int, nk: int) -> int:
    """The most q blocks that see one k block: queries ``k_start ..
    k_start + block_k + window - 2``."""
    return max(
        min((ki * block_k + block_k + window - 2) // block_q, nq - 1)
        - (ki * block_k) // block_q + 1
        for ki in range(nk))


def _div(a, b: int):
    """``a // b`` for a traced ``a >= 0``: one op where ``//`` lowers to a
    sign, a remainder and two selects in every index map."""
    return jax.lax.div(a, jnp.int32(b))


def _pad_to(x, multiple: int, axis: int):
    n = x.shape[axis]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block(s: int, largest: int) -> int:
    """The largest block that pads a sequence of ``s`` by at most an
    eighth (a block longer than ``s`` clamps to it at the call)."""
    block = largest
    while block > 128 and (-s) % block > s // 8:
        block //= 2
    return block


def _blocks(s_q: int, s_k: int, d: int, window: Optional[int] = None):
    """(block_q, block_k) of every kernel, from the shape. On a v5e,
    causal bf16, at 16 x 128 x s4096 and at 12 x 64 x s1024 (my chip runs,
    PR 36) — forward: 1024 x 1024 0.84 and 0.49 ms, the fastest; 512 x 512
    1.50 and 0.82, 256 x 256 2.85 and 1.34; 2048 x 2048 does not fit VMEM.
    dQ + dK/dV: 1024 x 1024 2.31 and 1.60, 512 x 512 2.39 and 1.61, 256 x
    256 4.03 and 2.34; 1024 x 2048 does not fit (the backward kernels hold
    p, dp and ds, three float32 [bq, bk] temporaries). A ``window`` shorter
    than that block caps both at ``WINDOW_BLOCK``: at 64 heads over 8 key
    heads x 128 x s8192, window 512 (my chip runs, PR 47), forward +
    backward 512 x 512 13.87 ms, 256 x 512 15.90, 512 x 1024 16.60, 1024 x
    1024 18.64, five more 19.05-35.66; the forward alone is fastest at 512 x
    1024 (4.93 against 5.80). A window of a whole block or more keeps 1024 x
    1024: at 2 x 32 heads over 4 key heads x 128 x s8192, window 1024 (my
    chip runs, PR 58), forward / fused backward in ms: 1024 x 1024 5.90 /
    11.08, 512 x 1024 6.56 / 11.38, 512 x 512 8.41 / 9.55, 256 x 1024 7.73 /
    11.11, 256 x 512 9.04 / 11.13, 1024 x 512 9.82 / 11.24 (a step under
    remat runs the forward twice: 22.89 against 512 x 512's 26.37). Heads
    256 wide, 16 over 2 key heads x s8192 | s16384 (my chip runs, PR 50),
    forward / dQ + dK/dV in ms: 1024 x 1024 5.56 / 14.59 | 16.84 / 48.50,
    the fastest of ten both ways; 512 x 1024 5.91 / 14.81 | 17.84 / 49.51,
    1024 x 512 6.17 / 14.66 | 19.12 / 50.01, 512 x 512 6.91 / 14.70 | 22.16
    / 50.92, the six smaller pairs 6.39-12.70 / 15.29-19.37. Keys 192 /
    values 128, 32 heads (my chip runs, PR 51; the same ten, the call's
    layout copies in both figures): 1024 x 1024 9.26 / 24.62 | 28.64 /
    83.06, the fastest again; 512 x 1024 10.43 / 25.26 | 32.89 / 85.77, 1024
    x 512 14.44 / 25.08 | 49.14 / 85.51, 512 x 512 (this width's until then)
    15.04 / 25.83 | 51.76 / 90.51, the six smaller pairs 12.41-27.56 /
    26.40-43.89. One rule for four widths. The fused backward
    (``backward_form``) was swept at three shapes over four pairs (PR 57,
    its docstring): 1024 x 1024 again, so forward, fused backward and pair
    share one rule."""
    largest = 1024
    if window is not None and window < largest:
        largest = WINDOW_BLOCK
    return _block(s_q, largest), _block(s_k, largest)


def _clamped(s_q: int, s_k: int, block_q: int, block_k: int) -> tuple:
    """Blocks no longer than their sequence (8 rows at the least)."""
    return min(block_q, max(s_q, 8)), min(block_k, max(s_k, 8))


def _fused_vmem_bytes(bq: int, bk: int, sk_p: int, d: int, d_v: int,
                      itemsize: int) -> int:
    """What one call of the fused backward holds in VMEM, counted as the
    chip lays it out: a row of any width takes whole 128-lane tiles (64
    wide takes 128, 192 takes 256), and the pipeline keeps two buffers of
    every block it copies. Float32 dK and dV over the padded keys; their
    output blocks, as long, twice; a block's p, dP and dS in float32 and
    p and dS again in the operands' dtype; q, dO, k and v blocks, twice;
    the logsumexp and row-sum columns (8 lanes asked, 128 held), twice;
    dQ's float32 sum and its output block, twice. The whole count and not
    the sums alone: at width 64 and 32,768 keys over two heads the
    compiler takes 76 MiB for the call, 32 of them the sums (PR 57)."""
    lanes_k, lanes_v = -(-d // 128) * 128, -(-d_v // 128) * 128
    lanes = lanes_k + lanes_v
    sums = sk_p * lanes * 4
    outputs = 2 * sk_p * lanes * itemsize
    temporaries = bq * bk * (3 * 4 + 2 * itemsize)
    operands = 2 * (bq + bk) * lanes * itemsize
    columns = 2 * 2 * bq * 128 * 4
    dq = bq * lanes_k * (4 + 2 * itemsize)
    return sums + outputs + temporaries + operands + columns + dq


def backward_form(s_q: int, s_k: int, d: int, d_v: int, groups: int,
                  window: Optional[int] = None, itemsize: int = 2) -> str:
    """``"fused"`` or ``"pair"``: the backward pass of a call of these
    shapes. One algorithm, two schedules: ``bps_flash_bwd`` walks dQ's grid
    (query blocks outside, key blocks inside), computes a block once and
    keeps float32 dK and dV of one key head over all its keys in VMEM, so
    it runs where what its call holds there (``_fused_vmem_bytes``, of the
    blocks ``_blocks`` gives, the keys padded to them and operands of
    ``itemsize`` bytes, bf16 unless said) is within ``_FUSED_VMEM_LIMIT``;
    a longer sequence keeps the pair, which recomputes the block in each
    kernel and holds a block's sums alone.
    Neither the group nor the window moves the count of the sums (the
    group's heads add into the same sums, a window's grid still spans the
    sequence); a window's smaller blocks shrink the temporaries. The last
    lengths that read "fused": 37,888 keys at widths 64 and 128, 24,576 at
    192 / 128, 18,432 at 256, 45,568 under a window of 512. Each compiles
    for a v5e (``tests/test_chip_compile.py``); the compiler's own count
    there is 86-87 MiB of the 95-96 counted here (93 of 95.5 under the
    window) and never more over 40 shapes compiled (PR 57): it keeps one
    buffer of an output block where there is one key head in all. Past
    those lengths the pair is held to compile and to the same gradients,
    not to a speed: no benchmark cell sends such a call, and at every last
    length the fused kernel was still the faster, so the limit is VMEM's.

    dQ, dK and dV are the pair's to the last bit on the chip too, at the
    twelve shapes below. On a v5e, causal bf16, the backward alone with its
    layout copies and row sums, pair | fused, ms (my chip runs, PR 57,
    every call asking 96 MiB): 32 x 192 / 128 x s8192 23.70 | 17.80, b8 x
    12 x 64 x s1024 1.607 | 1.388, 16 x 128 x s4096 2.600 | 2.178, 48 over
    8 x 128 x s8192 23.27 | 17.04, 64 over 8 under a window of 512 9.58 |
    7.50, 8 over 2 x 128 x s16384 13.70 | 10.20, 16 over 2 x 256 x s16384
    47.57 | 35.52; at the last lengths, 4 heads over 2 key heads (4 over 4
    at 192 / 128, 8 over 2 under the window): 34.13 | 23.90 at 64, 34.00 |
    24.18 at 128, 22.14 | 16.13 at 192 / 128, 15.32 | 11.64 at 256, 7.55 |
    6.03 under the window. Under a call limit of 64 MiB (an earlier call of
    PR 57, which the rest of this paragraph is from) the seven fused
    figures read 17.70, 1.393, 2.125, 16.76, 7.43, 10.07, 34.92, and
    JoyAI's the same 17.70 at 100. The other schedule (key blocks outside
    as dK/dV's grid, float32 dQ of a group's heads full length: ``groups x
    s_q x d x 4`` bytes, 134 MB at the seventh shape, which does not fit)
    read 16.78, 1.384, 2.017, 16.18 and 9.78 at the five shapes it fits:
    1-5% ahead, and not kept -- one rule that covers every shape the
    benchmark sends against two. The fused kernel at other blocks than
    ``_blocks``' (whose sums would change order): 1024 x 1024 17.70 | 10.07
    | 1.393 at the first, sixth and second shape, 1024 x 512 17.96 | 10.29
    | 1.385, 512 x 1024 18.11 | 10.56 | 1.392, 512 x 512 18.44 | 11.22 |
    1.390 (the pair at 512 x 512 25.01 at the first)."""
    bq, bk = _clamped(s_q, s_k, *_blocks(s_q, s_k, d, window))
    held = _fused_vmem_bytes(bq, bk, -(-s_k // bk) * bk, d, d_v, itemsize)
    return "fused" if held <= _FUSED_VMEM_LIMIT else "pair"


def window_walked_pairs(s_q: int, s_k: int, d: int, window: int) -> int:
    """The (query, key) pairs one head of one sequence costs a windowed
    call: every block its grids compute (``_window_k_blocks``, the rest of
    a grid is skipped), whole."""
    bq, bk = _clamped(s_q, s_k, *_blocks(s_q, s_k, d, window))
    nq, nk = -(-s_q // bq), -(-s_k // bk)
    return sum(_window_k_blocks(window, bq, bk, nq, nk)) * bq * bk


def block_census(s_q: int, s_k: int, d: int,
                 window: Optional[int] = None) -> tuple:
    """(live, interior): the blocks one head of one sequence costs a causal
    call — those its grids compute (``_window_k_blocks``; no window is one
    as long as the queries) — and, of them, those that run with no mask
    formed, by the predicate the kernels ask (``_interior``, as the
    backward's ask it: a block with padded queries is an edge block)."""
    bq, bk = _clamped(s_q, s_k, *_blocks(s_q, s_k, d, window))
    nq, nk = -(-s_q // bq), -(-s_k // bk)
    live = sum(_window_k_blocks(window or s_q, bq, bk, nq, nk))
    interior = sum(
        bool(_interior(qi * bq, ki * bk, bq, bk, s_q, s_k, True, window))
        for qi in range(nq) for ki in range(nk))
    return live, interior


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention over [batch, seq, heads, head_dim] arrays.

    ``block_q`` / ``block_k`` left ``None`` are derived from the shape
    (``_blocks``; the backward kernels take theirs from it whatever is
    passed here). Blocks clamp to the sequence length for short inputs.

    ``window`` (requires ``causal``) restricts each query to the last
    ``window`` positions — Mistral-style sliding-window attention; blocks
    left of every query's window are skipped entirely, so compute scales
    with ``seq * window`` instead of ``seq^2 / 2``.

    Exact softmax attention, O(seq) memory. ``interpret=None`` compiles
    the kernel on a TPU backend and interprets it elsewhere (tests run the
    same kernel on CPU); pass ``False`` to refuse interpretation. Drop-in for
    ``byteps_tpu.parallel.full_attention`` (which calls this itself for the
    shapes where the kernel wins on the chip), including as the inner
    kernel of ``ulysses_attention(attn_fn=...)``.
    """
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
                      window)[0]


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    """``None`` means: the compiled Mosaic kernel on a ``tpu`` backend —
    never the interpreter there — and the Pallas interpreter elsewhere
    (the CPU tests). A caller that must not run interpreted (a chip
    measurement) passes ``interpret=False``, which fails off-TPU instead
    of quietly interpreting."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _kv_head(heads: int, kv_heads: int):
    """Index map's part for grouped keys: row ``bh`` of [batch * heads] ->
    its row of [batch * kv_heads], query head i reading key head ``i //
    groups``. Equal counts: the row itself, and the text they lowered to."""
    if heads % kv_heads:
        raise ValueError(f"{heads} query heads do not divide over "
                         f"{kv_heads} key heads")
    groups = heads // kv_heads
    if groups == 1:
        return lambda bh: bh
    return lambda bh: _div(bh, groups)


def _to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window"))
def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                    window):
    """(out, logsumexp rows padded to [batch * heads, s_q', 8]). The one
    forward there is: a call that needs no residual drops the logsumexp
    (2 MB at 16 x s4096) and shares this trace with the one that does."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True (sliding-window "
                         "attention is a causal scheme)")
    b, s_q, h, d = q.shape
    s_k, d_v = k.shape[1], v.shape[-1]
    kv_head = _kv_head(h, k.shape[2])
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    bq, bk = _clamped(s_q, s_k, block_q, block_k)

    qq = _pad_to(_to_bhsd(q), bq, axis=1)
    kk = _pad_to(_to_bhsd(k), bk, axis=1)
    vv = _pad_to(_to_bhsd(v), bk, axis=1)
    sq_p, sk_p = qq.shape[1], kk.shape[1]

    nk = sk_p // bk
    if window is not None:
        # visit only the live k blocks per q block: grid work (and the
        # BlockSpec K/V prefetches) scale with seq*window, not seq^2
        nkg = _window_live_blocks(window, bq, bk, sq_p // bq, nk)

        def kv_index(bh, qi, ki):
            # past the diagonal: the last live block again, no copy
            return (kv_head(bh), jnp.minimum(
                _window_start_block(qi * bq, window, bk) + ki,
                jnp.minimum(_div(qi * bq + bq - 1, bk), nk - 1)), 0)
    elif causal:
        nkg = nk

        def kv_index(bh, qi, ki):
            # a block above the diagonal repeats the last live one's
            # index: the kernel skips it and the pipeline copies nothing
            return (kv_head(bh), jnp.minimum(ki, _div(qi * bq + bq - 1, bk)),
                    0)
    else:
        nkg = nk

        def kv_index(bh, qi, ki):
            return (kv_head(bh), ki, 0)

    grid = (b * h, sq_p // bq, nkg)
    scratch = [
        _VMEM((bq, 128), jnp.float32),  # m (value in lane 0)
        _VMEM((bq, 128), jnp.float32),  # l (value in lane 0)
        _VMEM((bq, d_v), jnp.float32),  # acc
    ]
    vmem = pl.BlockSpec
    in_specs = [
        vmem((1, bq, d), lambda bh, qi, ki: (bh, qi, 0),
             memory_space=_VMEM),
        vmem((1, bk, d), kv_index, memory_space=_VMEM),
        vmem((1, bk, d_v), kv_index, memory_space=_VMEM),
    ]
    o_spec = vmem((1, bq, d_v), lambda bh, qi, ki: (bh, qi, 0),
                  memory_space=_VMEM)
    o_shape = jax.ShapeDtypeStruct((b * h, sq_p, d_v), q.dtype)
    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  seq_k=s_k, window=window,
                  nk_total=nk if window is not None else None)
    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, **common),
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            o_spec,
            # lane dim 8 (not 128): the smallest layout-legal tile —
            # the kernels only read one value per row
            vmem((1, bq, 8), lambda bh, qi, ki: (bh, qi, 0),
                 memory_space=_VMEM),
        ],
        out_shape=[
            o_shape,
            jax.ShapeDtypeStruct((b * h, sq_p, 8), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
        name=FWD_NAME,
    )(qq, kk, vv)
    return _from_bhsd(out[:, :s_q], b, h), lse


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               window):
    derived = _blocks(q.shape[1], k.shape[1], q.shape[-1], window)
    out, lse = _flash_fwd_impl(
        q, k, v, causal, scale, block_q or derived[0], block_k or derived[1],
        _resolve_interpret(interpret), window)
    return out, (q, k, v, out, lse)


def _bwd_live(q_start, k_start, bq, bk, seq_q, seq_k, causal, window):
    """(live, interior): the block-level predicates shared by the backward
    kernels, which block to skip and which to run with no mask."""
    interior = _interior(q_start, k_start, bq, bk, seq_q, seq_k, causal,
                         window)
    if not causal:
        return True, interior
    live = q_start + bq - 1 >= k_start
    if window is not None:
        live = jnp.logical_and(live,
                               k_start + bk - 1 >= q_start - (window - 1))
    return live, interior


def _bwd_recompute(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                   q_start, k_start, masked, *, scale, causal, block_q,
                   block_k, seq_q, seq_k, window=None):
    """A block's recompute, shared by the fused kernel and the pair:
    returns (p, ds), float32; ``masked`` False on an interior block, whose
    mask is all true and is not formed. The one place the
    score/probability/ds math lives, so the backward kernels cannot
    silently diverge."""
    lse = lse_ref[0][:, 0:1]
    dd = dd_ref[0][:, 0:1]
    sc = jax.lax.dot_general(
        q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale
    if masked:
        p = jnp.where(_mask(q_start, k_start, block_q, block_k, seq_q, seq_k,
                            causal, window), jnp.exp(sc - lse), 0.0)
    else:
        p = jnp.exp(sc - lse)
    dp = jax.lax.dot_general(
        do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - dd) * scale


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref,
                      dq_acc, *, scale, causal, block_q, block_k,
                      seq_q, seq_k, window=None, nk_total=None):
    """dQ = scale * sum_k [p * (dO V^T - D)] K; grid (bh, qi, ki).
    ``nk_total`` set: ki counts from the first k block of the q block's
    window (the forward's restricted grid), of ``nk_total`` in all."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    step, q_start = ki, qi * block_q
    if nk_total is not None:
        ki = _window_start_block(q_start, window, block_k) + ki
    k_start = ki * block_k

    def _compute(masked):
        _, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_start, k_start,
            masked, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, seq_q=seq_q, seq_k=seq_k, window=window)
        k = k_ref[0]
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live, interior = _bwd_live(q_start, k_start, block_q, block_k, seq_q,
                               seq_k, causal, window)
    if nk_total is not None:
        live = jnp.logical_and(live, k_start < nk_total * block_k)
    _when_live(live, interior, _compute)

    @pl.when(step == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                       block_q, block_k, seq_q, seq_k, window=None,
                       q_of_step=None, nq_total=None):
    """dK = scale * sum_q ds^T Q;  dV = sum_q p^T dO; grid (bh, ki, qi).
    ``q_of_step(ki, step)``: the q block of the third grid position, where
    it is not the position itself (a group's heads one after another over
    one key head; a window's q blocks alone, of ``nq_total`` in all)."""
    ki, qi = pl.program_id(1), pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    step = qi
    if q_of_step is not None:
        qi = q_of_step(ki, qi)
    q_start = qi * block_q
    k_start = ki * block_k

    def _compute(masked):
        p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_start, k_start,
            masked, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, seq_q=seq_q, seq_k=seq_k, window=window)
        q, do = q_ref[0], do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live, interior = _bwd_live(q_start, k_start, block_q, block_k, seq_q,
                               seq_k, causal, window)
    if nq_total is not None:
        live = jnp.logical_and(live, q_start < nq_total * block_q)
    _when_live(live, interior, _compute)

    @pl.when(step == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fa_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref,
                         dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                         scale, causal, block_q, block_k, seq_q, seq_k,
                         groups, window=None, nk_total=None):
    """All three gradients from one recompute of a block; grid (bh, qi, ki)
    as dQ's, ``nk_total`` as there. dQ's sum over the inner axis sits in
    ``dq_acc`` [block_q, d]; dK's and dV's, over the outer axis and a
    group's heads, in ``dk_acc`` / ``dv_acc`` as long as the padded keys,
    float32, from a key head's first step to its last, when they leave in
    the operands' dtype as one block. A key block's contributions arrive
    in the order the dK/dV kernel adds them (head of the group, then q
    block), a query block's in dQ's: the sums are the pair's bit for bit."""
    bh, qi, ki = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    nq, nk = pl.num_programs(1), pl.num_programs(2)
    head = jax.lax.rem(bh, jnp.int32(groups))     # of its key head's group
    blocks = dk_acc.shape[0] // block_k

    def rows(i):
        return pl.ds(pl.multiple_of(i * block_k, block_k), block_k)

    @pl.when(jnp.logical_and(jnp.logical_and(qi == 0, ki == 0), head == 0))
    def _init_keys():
        def zero(i, _):
            dk_acc[rows(i), :] = jnp.zeros((block_k, dk_acc.shape[1]),
                                           jnp.float32)
            dv_acc[rows(i), :] = jnp.zeros((block_k, dv_acc.shape[1]),
                                           jnp.float32)
        jax.lax.fori_loop(0, blocks, zero, None)

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    step, q_start = ki, qi * block_q
    if nk_total is not None:
        ki = _window_start_block(q_start, window, block_k) + ki
    k_start = ki * block_k

    def _compute(masked):
        p, ds = _bwd_recompute(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, q_start, k_start,
            masked, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, seq_q=seq_q, seq_k=seq_k, window=window)
        q, k, do = q_ref[0], k_ref[0], do_ref[0]
        dv_acc[rows(ki), :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = ds.astype(q.dtype)
        dk_acc[rows(ki), :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    live, interior = _bwd_live(q_start, k_start, block_q, block_k, seq_q,
                               seq_k, causal, window)
    if nk_total is not None:
        live = jnp.logical_and(live, k_start < nk_total * block_k)
    _when_live(live, interior, _compute)

    @pl.when(step == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(jnp.logical_and(qi == nq - 1, step == nk - 1),
                             head == groups - 1))
    def _finish_keys():
        def cast(i, _):
            dk_ref[0, rows(i), :] = dk_acc[rows(i), :].astype(dk_ref.dtype)
            dv_ref[0, rows(i), :] = dv_acc[rows(i), :].astype(dv_ref.dtype)
        jax.lax.fori_loop(0, blocks, cast, None)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res,
               g):
    """Pallas backward: blockwise recompute from (q, k, v, o, lse) — the
    standard flash-attention backward, O(seq) memory like the forward, in
    the form ``backward_form`` names for the shapes."""
    q, k, v = res[:3]
    bq, bk = _blocks(q.shape[1], k.shape[1], q.shape[-1], window)
    form = backward_form(q.shape[1], k.shape[1], q.shape[-1], v.shape[-1],
                         q.shape[2] // k.shape[2], window, q.dtype.itemsize)
    return _flash_bwd_impl(*res, g, causal, scale, bq, bk,
                           _resolve_interpret(interpret), window, form)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "window", "form"))
def _flash_bwd_impl(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret, window, form):
    b, s_q, h, d = q.shape
    s_k, d_v, h_kv = k.shape[1], v.shape[-1], k.shape[2]
    kv_head, groups = _kv_head(h, h_kv), h // h_kv
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    bq, bk = _clamped(s_q, s_k, block_q, block_k)

    qq = _pad_to(_to_bhsd(q), bq, axis=1)
    kk = _pad_to(_to_bhsd(k), bk, axis=1)
    vv = _pad_to(_to_bhsd(v), bk, axis=1)
    dd_o = _pad_to(_to_bhsd(g.astype(q.dtype)), bq, axis=1)
    sq_p, sk_p = qq.shape[1], kk.shape[1]

    # D_i = rowsum(dO * O), f32, one value per row in the 8-lane tile.
    dvec = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1)                                  # [b, s, h]
    dvec = dvec.transpose(0, 2, 1).reshape(b * h, s_q)
    dd = jnp.broadcast_to(_pad_to(dvec, bq, axis=1)[:, :, None],
                          (b * h, sq_p, 8))

    # the forward's lse is padded with the FORWARD's bq; re-pad for bwd
    lse = _pad_to(lse[:, :s_q], bq, axis=1)

    nq, nk = sq_p // bq, sk_p // bk
    banded = window is not None
    # a window's grids walk the band alone, as the forward's does: the
    # most k blocks a q block sees, the most q blocks that see a k block
    nk_dq = _window_live_blocks(window, bq, bk, nq, nk) if banded else nk
    nq_dkv = _window_live_q_blocks(window, bq, bk, nq, nk) if banded else nq

    def q_of_dq(bh, qi, ki):
        return (bh, qi, 0)

    def k_of_dq(bh, qi, ki):
        if banded:
            ki = _window_start_block(qi * bq, window, bk) + ki
        # above the diagonal: repeat the last live block, copy nothing
        if causal:
            ki = jnp.minimum(ki, _div(qi * bq + bq - 1, bk))
        if banded:
            ki = jnp.minimum(ki, nk - 1)
        return (kv_head(bh), ki, 0)

    # the third grid position of dK/dV -> (head of the group, q block)
    q_of_step = None
    if banded or groups > 1:
        def q_of_step(ki, step):
            if groups > 1:
                step = jax.lax.rem(step, jnp.int32(nq_dkv))
            return _div(ki * bk, bq) + step if banded else step

    def q_of_dkv(bh, ki, step):
        qi = step if q_of_step is None else q_of_step(ki, step)
        # left of the first query block that sees this key block: the same
        if causal and not banded:
            qi = jnp.maximum(qi, _div(ki * bk, bq))
        if banded:
            qi = jnp.minimum(qi, nq - 1)
        if groups > 1:
            bh = bh * groups + _div(step, nq_dkv)
        return (bh, qi, 0)

    def k_of_dkv(bh, ki, qi):
        return (bh, ki, 0)

    def specs(q_index, k_index):
        rows = [(bq, d, q_index), (bk, d, k_index), (bk, d_v, k_index),
                (bq, d_v, q_index), (bq, 8, q_index), (bq, 8, q_index)]
        return [pl.BlockSpec((1, n, w), index, memory_space=_VMEM)
                for n, w, index in rows]

    kw = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
              seq_q=s_q, seq_k=s_k, window=window)

    if form == "fused":
        def keys_of(bh, qi, ki):
            return (kv_head(bh), 0, 0)

        dq, dk, dv = pl.pallas_call(
            functools.partial(_fa_bwd_fused_kernel, **kw, groups=groups,
                              nk_total=nk if banded else None),
            grid=(b * h, nq, nk_dq),
            in_specs=specs(q_of_dq, k_of_dq),
            out_specs=[
                pl.BlockSpec((1, bq, d), q_of_dq, memory_space=_VMEM),
                pl.BlockSpec((1, sk_p, d), keys_of, memory_space=_VMEM),
                pl.BlockSpec((1, sk_p, d_v), keys_of, memory_space=_VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
                jax.ShapeDtypeStruct((b * h_kv, sk_p, d), k.dtype),
                jax.ShapeDtypeStruct((b * h_kv, sk_p, d_v), v.dtype),
            ],
            scratch_shapes=[_VMEM((bq, d), jnp.float32),
                            _VMEM((sk_p, d), jnp.float32),
                            _VMEM((sk_p, d_v), jnp.float32)],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=max(
                _fused_vmem_bytes(bq, bk, sk_p, d, d_v, q.dtype.itemsize),
                _VMEM_UNASKED)),
            interpret=interpret,
            name=BWD_NAME,
        )(qq, kk, vv, dd_o, lse, dd)
    else:
        dq = pl.pallas_call(
            functools.partial(_fa_bwd_dq_kernel, **kw,
                              nk_total=nk if banded else None),
            grid=(b * h, nq, nk_dq),
            in_specs=specs(q_of_dq, k_of_dq),
            out_specs=pl.BlockSpec((1, bq, d), q_of_dq, memory_space=_VMEM),
            out_shape=jax.ShapeDtypeStruct((b * h, sq_p, d), q.dtype),
            scratch_shapes=[_VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name=DQ_NAME,
        )(qq, kk, vv, dd_o, lse, dd)

        dk, dv = pl.pallas_call(
            functools.partial(_fa_bwd_dkv_kernel, **kw, q_of_step=q_of_step,
                              nq_total=nq if banded else None),
            grid=(b * h_kv, nk, groups * nq_dkv),
            in_specs=specs(q_of_dkv, k_of_dkv),
            out_specs=[
                pl.BlockSpec((1, bk, d), k_of_dkv, memory_space=_VMEM),
                pl.BlockSpec((1, bk, d_v), k_of_dkv, memory_space=_VMEM),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((b * h_kv, sk_p, d), k.dtype),
                jax.ShapeDtypeStruct((b * h_kv, sk_p, d_v), v.dtype),
            ],
            scratch_shapes=[_VMEM((bk, d), jnp.float32),
                            _VMEM((bk, d_v), jnp.float32)],
            interpret=interpret,
            name=DKV_NAME,
        )(qq, kk, vv, dd_o, lse, dd)

    dq = _from_bhsd(dq[:, :s_q], b, h)
    dk = _from_bhsd(dk[:, :s_k], b, h_kv)
    dv = _from_bhsd(dv[:, :s_k], b, h_kv)
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)
