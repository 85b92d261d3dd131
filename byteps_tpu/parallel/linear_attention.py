"""Gated delta-rule linear attention with a per-channel decay (KDA: Kimi
Linear, Moonshot AI 2025, arXiv 2510.26692; the public ``fla`` layer
``KimiDeltaAttention``) as a chunked scan, and its three siblings: one
decay a head (Gated DeltaNet), no delta rule at all (Mamba-2's state-space
scan), and one decay a channel and state entry (Mamba-1's selective
scan).

Per head, a float32 state ``S`` [d_k, d_v] (keys x values), ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,        alpha_t = exp(g_t) in (0, 1) per key channel.

Token by token that is s sequential rank-one updates. The chunked form
(the WY representation of the delta rule, with the decay carried as a
cumulated log-decay ``G_i = sum_{j<=i} g_j`` inside a chunk of ``C`` tokens)
leaves one sequential step a chunk. With ``u_i`` the value a token really
writes, ``S_i = Diag(e^{G_i}) S_0 + sum_{j<=i} Diag(e^{G_i - G_j}) k_j
u_j^T`` and

    P(a, b)[i, j] = sum_c a_ic b_jc e^{G_ic - G_jc}           (j <= i)
    (I + tril(beta_i P(k, k), -1)) U = beta (V - (K e^G) S_0)
    O = (Q e^G) S_0 + tril(P(q, k)) U
    S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U.

Everything but ``S_0`` is known before the scan: ``T = (I + A)^-1``,
``W = T (beta K e^G)`` and ``U_v = T (beta V)`` for all chunks at once, so
that the scan's body is ``U = U_v - W S``, the two products of ``O`` and
the state's update.

**No positive number is exponentiated.** ``P`` as the product
``(a e^G)(b e^{-G})^T`` overflows float32 as soon as a chunk's decay passes
e^-88, which Mamba's initialisation reaches. Here ``e^{G_i - G_j}`` is
formed for ``j <= i`` only: pairwise inside sub-chunks of ``sub`` tokens,
and between sub-chunks as ``(a_i e^{G_i - G_r}) (b_j e^{G_r - G_j})`` with
``r`` the first token of i's sub-chunk, so that ``j < r <= i`` and both
exponents are <= 0; pairs above the diagonal are masked to ``-inf`` before
the ``exp``. An underflow to 0 is the exact value to float32; the decay is
never clamped.

The unit-lower-triangular ``I + A`` is inverted by block forward
substitution, doubling the block: with ``T`` the inverse of the diagonal
blocks of size b, ``T - T (A . L_b) T`` (``L_b`` the lower-left quarter of
every 2b block) is the inverse of the blocks of size 2b; log2 C steps of
two [C, C] products of float32 operands in three bf16 passes.

Decay, cumulated decay, ``A``, ``T`` and the state are float32; the
operands of the other products are cast to ``dtype`` (bf16 on the chip)
and accumulate in float32.

**One algorithm, two forms of a chunk's operands** (``kda_form``, a pure
function of the backend and the shapes, as ``ring_attention.py::
attention_form`` is for exact attention; each counted at trace time). The
XLA form above, ``_chunk_operands``, is what every CPU run, float32
``dtype`` and any width but 128 x 128 gets: its pairs of a sub-chunk are a
float32 ``[.., sub, sub, d_k]`` tensor and its decayed keys ``[.., n, C,
d_k]``, written to HBM and read back, forward, recomputed and backward. On
a ``tpu`` backend, bf16 ``dtype``, keys and values 128 wide, heads in
eights and a chunk of a multiple of 8 up to 128 tokens, the kernel pair of
``byteps_tpu.ops.kda_chunk`` holds a chunk of all heads in VMEM: every
pair ``j <= i`` of the chunk one by one in float32 (no product between
sub-chunks is left), the triangular system by forward substitution in the
same walk (``T`` itself is never formed: ``W`` and ``U_v`` are), and a
hand-written backward kernel that solves the transposed system (``dA =
-T^T dT T^T`` below the diagonal, by back substitution) instead of
differentiating an inverse's steps. Same guarantees: no positive number
exponentiated, no clamped decay, float32 wherever the XLA form has it.

**One decay a head** (Gated DeltaNet, arXiv 2412.06464; ``g`` [b, s, h]
and not [b, s, h, d_k]): ``alpha_t`` is one number, the exponent of a pair
no longer turns on the channel, and

    P(a, b)[i, j] = (a_i . b_j) e^{G_i - G_j}                 (j <= i)

is one ``[C, d_k] x [d_k, C]`` product under a ``[C, C]`` mask of
exponentials, ``K e^G`` a scaling of rows, ``e^{G_C}`` one float a head. No
sub-chunk is needed for the rule above: ``G_i - G_j`` is formed for the
pair itself, masked to ``-inf`` above the diagonal before the ``exp``, and
never clamped. ``q`` and ``k`` may have fewer heads than ``v``, a divisor:
key head j serves value heads ``j groups .. (j + 1) groups - 1``, and a key
head's pairs are multiplied once. This rank has two forms too, under the
per-channel forms' rule of the shapes (``kda_form``). ``_head_operands``
(form ``"head"``: every backend, dtype and shape) builds a group's operands
in XLA, ``HEAD_GROUP`` chunks at a time, with chunking, the triangular
solve by doubling, the groups and ``_recurrence`` the per-channel XLA
form's own. On a ``tpu`` backend at the kernel shapes (form
``"head_kernel"``, since PR 59) the kernel pair of
``byteps_tpu.ops.gdn_chunk`` holds a chunk of all heads in VMEM: the pair
products once a key head on the MXU in three bf16 passes (the chip's
``EXACT``), the chunk's decay cumulated there, the triangular system by
forward substitution in float32 and, in a hand-written backward kernel that
keeps nothing of the forward, the transposed system by back substitution —
and leaves ``W``, ``U_v``, ``Q e^G``, ``K e^{G_C - G}``, ``e^{G_C}`` and
the pairs ``[b, n, C, h, d]`` in the dtypes the recurrence kernels read.
The decay broadcast over the channels into the per-channel kernels instead
(16 MB of float32 decay a layer and 1000 tokens) had moved no step at s
8,192 and did not compile in the step at 16,384 (PERF.md section 6, PR 50).

**No delta rule** (the selective state-space recurrence of Mamba-2, Dao &
Gu 2024, arXiv 2405.21060; ``ssd_scan``): per head a float32 state ``S``
[n, p] (state entries x channels) under one decay a head,

    S_t = e^{g_t} S_{t-1} + B_t (dt_t x_t)^T,        y_t = S_t^T C_t,

``C`` the query, ``B`` the key, ``x`` the value and the step ``dt`` the
write strength, with ``B`` and ``C`` in groups under the heads as key heads
lie under value heads above. It is the per-head form with the triangular
system gone: a token writes ``dt x`` whatever the state holds, so ``U`` is
``dt x`` itself, ``W`` is zero, and the pairs are ``P(C, B)[i, j] = (C_i .
B_j) e^{G_i - G_j}`` under the same rule — masked to ``-inf`` above the
diagonal before the ``exp``, never clamped. With no ``W S`` nothing of a
chunk but its first state waits for the chunk before it: a group's pairs,
its products with the writes and every chunk's share of the next state
``(B e^{G_C - G})^T U`` are batched products over the group's chunks, the
pairs and that share multiplied once a *group* of ``B`` and ``C`` and not
once a head, and what runs chunk after chunk is ``S <- e^{G_C} S + Z``.
It shares ``chunked``, ``chunk_log_decay``, the pair-and-mask code and the
two-level scan over groups of ``SSD_GROUP`` chunks. This state is 128 x 64
a head at the one configuration that runs it and both kernel pairs above
are 128 x 128, so ``kda_form`` has no answer for it: it has a rule of its
own, ``ssd_form``, and two forms under it (each counted at trace time:
``bps_ssm_scan_sites_total``, ``bps_ssm_scan_kernel_sites_total``). The XLA
form, ``_ssd_group``, is what every CPU run, float32 ``dtype`` and any
shape but the rule's gets: its masks ``e^{G_i - G_j}`` are a float32
``[b, group, heads, C, C]`` tensor in HBM, its state goes through HBM
around every chunk, and it takes ``x`` ``[b, s, heads, p]``. On a ``tpu``
backend, bf16 ``dtype``, a state of 128 entries, heads of 64 channels in
eights a group and chunks of 128, the kernels of
``byteps_tpu.ops.ssd_scan`` (since PR 65) hold every group's state in VMEM
from the first chunk to the last, form a chunk's masks there, and read
``x``, ``B`` and ``C`` — and write ``y`` and their cotangents — in the
``[s, channels]`` layout the convolution before the scan and the gate after
it use (``ssd_scan_channels`` is that entry; a hand-written backward kernel
under a states-only forward walk). Same guarantees: float32 state, bf16
operands with float32 accumulation, masked to ``-inf`` before the ``exp``,
nothing clamped.

**One decay a channel and state entry** (Mamba-1's selective scan, Gu &
Dao 2023, arXiv 2312.00752; ``selective_scan``): per channel a float32
state ``S`` [n] under the transition ``exp(dt_t[c] a[c, n])``,

    S_t[c, n] = exp(dt_t[c] a[c, n]) S_{t-1}[c, n] + dt_t[c] b_t[n] x_t[c],
    y_t[c] = sum_n c_t[n] S_t[c, n].

The decay of a pair of tokens turns on the channel *and* the state entry,
so no pair product factors into a matmul (the per-head form's ``[C, C]``
mask, the per-channel form's sub-chunk products and ``ops/ssd_scan.py``'s
chunked matmuls all need a decay that is one number a head or a key
channel): what the recurrence needs is 5 elementwise operations a token,
channel and state entry, and this is that recurrence itself, token by
token, with no chunk algebra. It shares ``chunked`` and the rule of the
two-level scan — the state alone is kept from chunk to chunk and a chunk
of ``SEL_CHUNK`` tokens is recomputed in the backward pass, so that the
[s, n, channels] history (5.4 GB in float32 at s 16,384 x 16 x 5,120) never
exists — with the state laid ``[n, channels]``: state entries on sublanes,
channels on lanes. One form, every backend (``SEL_CHUNK`` has the sweep);
counted at trace time (``bps_sel_scan_sites_total``). No decay is clamped
and none cumulated: ``exp`` is taken of a token's own ``dt a <= 0``, and an
underflow to 0 is the exact float32 value.

**The scan over chunks has two forms too, and the operands' form picks
it.** Where XLA builds the operands (``"xla"`` and ``"head"``) the scan has
two levels, groups of chunks and the chunks of a group, and its backward
pass is ``jax.grad`` through both, a group recomputed at a time
(``jax.checkpoint``): the scan keeps one state a group, and a group's
operands are computed inside the group (the per-channel form's pair tensor
bounds the group, the per-head form's is ``HEAD_GROUP`` chunks).
``_recurrence`` there is an XLA ``while`` iteration of four small products a
chunk, the float32 state (2 MB at 32 heads) through HBM around each. A
hand-written backward of it *in XLA* was measured against ``jax.grad`` and
lost (PERF.md section 6, PR 39) while the operands were most of the scan;
since their kernel (PR 40) the recurrence, the slices and copies of the
``while`` over groups and the copy that laid the operands out chunk-major
for it were the larger half of the kernel form's scan, bound by nothing the
chip has.

The kernel forms compute every chunk's operands in one call before the
scan and leave them ``[b, n, C, h, d]``, and since PR 54 the kernel pair of
``byteps_tpu.ops.kda_recurrence`` runs all chunks as one call over them as
they lie, the state in VMEM from the first chunk to the last: no scan over
groups, no layout copy (PERF.md section 6, PR 54). Its backward is
hand-written (``custom_vjp``): the forward kernel leaves the state every 16
chunks (what the scan over groups kept), and one backward kernel walks each
such group forward again, leaving its states in VMEM, and back, carrying
``dS`` from group to group. Same guarantees: the carried state float32 and
never rounded, the state and ``U`` rounded to ``dtype`` as matmul operands
only, float32 accumulation. It is the layout that decides: the per-head
operands as XLA builds them come a few chunks at a time with heads before
tokens, and laid out anew for a call of the pair at 4 to 32 chunks they did
not beat ``_recurrence`` (the pair ahead for the recurrence alone, behind in
the whole op by the layout copies: PR 54); the per-head kernels write the
pair's own layout. One call's operands and, in the backward pass, their
cotangents are alive at once, where the XLA forms hold a few chunks': 0.7
GB and as much again a layer at the Qwen3-Next cell's 512 chunks, which the
compiled step holds within the XLA form's peak. Cutting the sequence into
calls with the state handed on was measured and made both worse (PERF.md
section 6, PR 59): there is one call.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from byteps_tpu.monitor import metrics

# As a device trace names the op's two parts (``jax.named_scope``), and the
# counter of its call sites at trace time.
PREP_SCOPE, SCAN_SCOPE = "bps.kda.prep", "bps.kda.scan"
# ... where the decay is one number a head (Gated DeltaNet)
GDN_PREP_SCOPE, GDN_SCAN_SCOPE = "bps.gdn.prep", "bps.gdn.scan"
SCAN_SITES = "bps_kda_scan_sites_total"
# ... and of those that took the kernel form of a chunk's operands
KERNEL_SITES = "bps_kda_kernel_sites_total"
# ... or the per-head form
HEAD_SITES = "bps_kda_head_sites_total"
# ... and of those, the ones whose operands are the kernel pair of
# ``byteps_tpu.ops.gdn_chunk``
HEAD_KERNEL_SITES = "bps_kda_head_kernel_sites_total"
# ... where there is no delta rule at all (the selective state-space scan),
# with its own counters: ``ssd_form`` answers for it, not ``kda_form``
SSM_PREP_SCOPE, SSM_SCAN_SCOPE = "bps.ssm.prep", "bps.ssm.scan"
SSM_SCAN_SITES = "bps_ssm_scan_sites_total"
# ... and of those, the ones that took the kernels of ``ops/ssd_scan.py``
SSM_SCAN_KERNEL_SITES = "bps_ssm_scan_kernel_sites_total"
# ... and where the decay is one number a channel AND state entry (Mamba-1's
# selective scan): one form, every backend
SEL_PREP_SCOPE, SEL_SCAN_SCOPE = "bps.sel.prep", "bps.sel.scan"
SEL_SCAN_SITES = "bps_sel_scan_sites_total"
# ``checkpoint_name`` of the states it keeps, one a chunk
SEL_STATES = "sel_chunk_states"
# ... and of all scan sites, those whose scan over chunks is the kernel pair
# of ``byteps_tpu.ops.kda_recurrence`` (both kernel forms': it is their
# layout it reads)
RECURRENCE_KERNEL_SITES = "bps_kda_recurrence_kernel_sites_total"

# What the kernel of ``byteps_tpu.ops.kda_chunk`` was measured at against
# the XLA form on a TPU v5e and won (PERF.md section 6, PR 40): keys and
# values 128 wide (a token of a head is one row of lanes), bf16 operands.
# Its tiling admits any chunk of whole sublane groups (a multiple of 8) up
# to a row of lanes (128), and heads in whole sublane groups.
KERNEL_WIDTH = 128

# The per-head kernels of ``byteps_tpu.ops.gdn_chunk`` hand the compiler
# every row and pair of a chunk as straight-line code, about C^2 / 2 pairs a
# walk, and ask of VMEM by the rows (tokens x value heads) a chunk holds.
# Chunks of 32 are what the benchmark runs and what was measured; at 64 the
# backward kernel took 98 s to compile for a v5e (21 s at 32) and minutes
# interpreted, for a chunk no configuration uses: the XLA form keeps it.
# 1024 rows a chunk are the cell's 32 x 32, which ask 32 MiB of VMEM: the
# most that ran on the chip, and with 64 heads x 16 and 128 x 8 the edges
# that tests/test_chip_compile.py compiles for a v5e.
HEAD_KERNEL_CHUNK, HEAD_KERNEL_ROWS = 32, 1024

# The chunks of a group in the per-head form, whose operands are computed
# (and, in the backward pass, recomputed and differentiated) a group at a
# time. On a TPU v5e at [1, 8192, 32, 128] over 16 key heads, bf16, chunks of
# 32, forward + backward (PERF.md section 6, my chip runs, PR 50): 4 chunks
# 22.8 ms, 16 chunks 32.0, 32 chunks 31.9; at s 16384 4 chunks 44.6, 8 45.7.
# A group's scan as a call of the recurrence kernels, its operands laid out
# for it, at s 16384 (my chip runs, PR 54): 4 chunks 52.5 ms, 8 51.1, 16
# 53.3, 32 59.2 against ``_recurrence`` at 4 chunks 50.9, which stays.
HEAD_GROUP = 4

# The chunks of a group in the state-space scan, whose pairs, chunk states
# and products are computed (and recomputed, and differentiated) a group at
# a time: everything of a group but the few multiply-adds that hand the
# state from chunk to chunk is one batched product over its chunks. On a TPU
# v5e at [1, 16384, 64, 64] over 8 groups of state 128, bf16, forward +
# backward (PERF.md section 6, my chip runs, PR 63): chunks of 128 in groups
# of 1 29.3 ms, 2 20.5, 4 20.5, 8 20.0 (18.6 at 4 in that call), 16 28.0,
# 32 32.2; chunks of 256 in groups of 1 22.5, 2 20.3, 4 21.0, 8 28.3; chunks
# of 64 35.5 at 8 and worse above; 512 27.2 at 1. Flat from 2 to 8 at 128:
# 4 holds half the float32 masks of 8 alive.
SSD_GROUP = 4

# The tokens of a chunk of ``selective_scan``: the scan keeps one state a
# chunk and its backward pass recomputes a chunk at a time. On a TPU v5e for
# the op alone at [1, 16384, 5120] x 16 states, float32, forward | forward +
# backward (PERF.md section 6, my chip runs, PR 71; two calls): the two loops
# below — a ``lax.scan`` over a chunk's tokens inside the scan over chunks,
# in the sweep under ``jax.checkpoint`` and ``jax.grad`` (the committed
# backward rule does the same work; 8.2 | 26.6 ms at s 8,192, ``tools/
# scan_check.py --cases sel``) — at 32 tokens 14.0 | 67.6 ms, 64 13.4 | 65.9,
# 128 13.4 | 88.2, 256 14.7 | 116.4, 1024 13.2 | 125.4; unrolled twice at 64
# 13.2 | 69.3, four times at 128 12.8 | 94.1. A chunk's tokens written out as one straight
# elementwise chain (no inner loop): 4 tokens 18.4 | 70.4 (3.0 GB of kept
# states), 8 16.6 | 74.1, 16 13.3 | 105.0, 32 12.8 | 167.4; the same with
# the chunk's states stacked and ``C S`` one reduction after the chain 17.3
# | 73.8 at 8, 16.9 | 84.6 at 16, 16.2 | 133.7 at 32; ``lax.
# associative_scan`` inside a chunk 42.4 | 115.6 at 16 and 38.6 | 271.3 at
# 64. Every form's forward pass sits at 0.8 us a token, the float32 state
# [16, 5120] (320 KB) read and written once a token; the backward pass
# moves a chunk's states and decays besides.
SEL_CHUNK = 64

# float32 operands as three bf16 passes: the triangular system's inverse and
# the products between sub-chunks need more than the one pass a TPU gives a
# float32 product by default, and what they feed is rounded to ``dtype``
# afterwards: the six passes of HIGHEST would be spent on digits that round
# away (they were 120 ms of a 2,022 ms step at s 16384; PERF.md, PR 39)
EXACT = lax.Precision.HIGH


def chunked(x: jax.Array, chunk: int) -> jax.Array:
    """[b, s, ...] -> [b, ceil(s / chunk), chunk, ...], zeros after s. A
    zero token (k = 0, beta = 0, g = 0) leaves the state as it is."""
    b, s = x.shape[:2]
    n = -(-s // chunk)
    x = jnp.pad(x, [(0, 0), (0, n * chunk - s)] + [(0, 0)] * (x.ndim - 2))
    return x.reshape(b, n, chunk, *x.shape[2:])


def chunk_log_decay(g: jax.Array, chunk: int) -> jax.Array:
    """[b, n, chunk, h, ...] float32: ``g`` [b, s, h, d_k] (or [b, s, h],
    one decay a head) cumulated inside each chunk of ``chunk`` tokens."""
    return jnp.cumsum(chunked(g.astype(jnp.float32), chunk), axis=2)


def _decayed_products(rows, b, G, sub: int):
    """``P(a, b)`` for every ``a`` of ``rows`` [m, ..., C, d] against ``b``
    [..., C, d] under the cumulated log-decay ``G`` [..., C, d]: [m, ..., C,
    C], zero above the diagonal (module docstring)."""
    c, d = G.shape[-2:]
    n = c // sub
    lead = G.shape[:-2]

    def blocks(x):
        return x.reshape(*x.shape[:-2], n, sub, d)

    rows_, b_, G_ = blocks(rows), blocks(b), blocks(G)
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where(
        (j <= i)[:, :, None], G_[..., :, None, :] - G_[..., None, :, :],
        -jnp.inf))                                     # [..., n, i, j, d]
    diag = (rows_[..., :, None, :] * (b_[..., None, :, :] * decay)).sum(-1)
    # [m, ..., n, i, j] -> [m, ..., (n, i), (n', j)]: the diagonal blocks
    out = (diag[..., :, :, None, :]
           * jnp.eye(n, dtype=diag.dtype)[:, None, :, None]).reshape(
               rows.shape[0], *lead, c, c)
    if n == 1:
        return out
    first = G_[..., 0, :]                              # [..., n, d]
    left = rows_ * jnp.exp(G_ - first[..., None, :])   # i in its sub-chunk
    earlier = (jnp.arange(c)[None, :]
               < (jnp.arange(n) * sub)[:, None])       # [n, C]: j < r
    right = b[..., None, :, :] * jnp.exp(jnp.where(
        earlier[:, :, None], first[..., :, None, :] - G[..., None, :, :],
        -jnp.inf))                                     # [..., n, C, d]
    off = jnp.einsum("m...nic,...njc->m...nij", left, right,
                     precision=EXACT)
    return out + off.reshape(rows.shape[0], *lead, c, c)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` [..., C, C]."""
    c = a.shape[-1]
    t = jnp.broadcast_to(jnp.eye(c, dtype=a.dtype), a.shape)
    block = 1
    while block < c:
        of = jnp.arange(c) // block
        lower_left = (of[:, None] == of[None, :] + 1) & (of[:, None] % 2 == 1)
        t = t - jnp.matmul(jnp.matmul(t, a * lower_left, precision=EXACT),
                           t, precision=EXACT)
        block *= 2
    return t


def _solved(t, beta, x, dtype):
    """``T (beta x)``: the triangular system's solution for the right-hand
    sides ``x`` [..., C, d], both operands in ``dtype``, float32 out."""
    return jnp.einsum("...ij,...jd->...id", t.astype(dtype),
                      (beta[..., None] * x).astype(dtype),
                      preferred_element_type=jnp.float32)


def _chunk_operands(q, k, v, beta, G, sub, dtype):
    """What the scan needs of every chunk and can have before it: ``W``,
    ``U_v``, ``Q e^G``, ``K e^{G_C - G}``, ``e^{G_C}`` and ``tril(P(q,
    k))`` (module docstring). q, k, G [..., C, d_k], v [..., C, d_v], beta
    [..., C], float32."""
    c = G.shape[-2]
    p_k, a_q = _decayed_products(jnp.stack([k, q]), k, G, sub)
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    t = _unit_lower_inverse(jnp.where(j < i, beta[..., None] * p_k, 0.0))
    total = G[..., -1:, :]                              # a chunk's whole decay
    # what only ever is a matmul operand is kept in ``dtype``
    return (_solved(t, beta, k * jnp.exp(G), dtype).astype(dtype),
            _solved(t, beta, v, dtype),
            (q * jnp.exp(G)).astype(dtype),
            (k * jnp.exp(total - G)).astype(dtype),
            jnp.exp(total[..., 0, :]), a_q.astype(dtype))


def _pair_decay(G, i, j):
    """``e^{G_i - G_j}`` for the pairs ``j <= i`` of a chunk, 0 above the
    diagonal: G [..., h, C], one decay a head; masked to ``-inf`` before
    the ``exp``, so that no positive number is exponentiated."""
    return jnp.exp(jnp.where(j <= i, G[..., :, None] - G[..., None, :],
                             -jnp.inf))


def _head_operands(q, k, v, beta, G, dtype):
    """``_chunk_operands`` for one decay a head (module docstring): G [...,
    h, C]; q, k [..., h_k, C, d_k] with ``h_k`` a divisor of h; v [..., h,
    C, d_v], beta [..., h, C], float32. ``e^{G_C}`` comes back [..., h, 1]:
    it scales every row of the state alike."""
    groups, c = G.shape[-2] // q.shape[-3], G.shape[-1]
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = _pair_decay(G, i, j)                        # [..., h, C, C]

    def pairs(a):       # a key head's products, under each of its decays
        return jnp.repeat(jnp.einsum("...id,...jd->...ij", a, k,
                                     precision=EXACT), groups, -3) * decay

    a_q = pairs(q)
    t = _unit_lower_inverse(jnp.where(j < i, beta[..., None] * pairs(k),
                                      0.0))
    q, k = (jnp.repeat(x, groups, -3) for x in (q, k))
    total = G[..., -1:]                                 # a chunk's whole decay
    e = jnp.exp(G)[..., None]
    return (_solved(t, beta, k * e, dtype).astype(dtype),
            _solved(t, beta, v, dtype), (q * e).astype(dtype),
            (k * jnp.exp(total - G)[..., None]).astype(dtype),
            jnp.exp(total), a_q.astype(dtype))


def kda_form(backend: str, heads: int, d_k: int, d_v: int, dtype,
             chunk: int, per_head: bool, key_heads: int) -> str:
    """``"head"``, ``"head_kernel"``, ``"kernel"`` or ``"xla"``: how
    ``kda_attention`` computes a chunk's operands at these shapes, and with
    them how it scans the chunks. One algorithm; a decay of either rank has
    an XLA form for every backend, dtype and shape (``"head"`` where
    ``per_head``, the decay one number a head, else ``"xla"``) and a kernel
    form (``"head_kernel"``, ``"kernel"``) under one rule of the shapes: a
    ``tpu`` backend, bf16 operands, keys and values ``KERNEL_WIDTH`` wide,
    the heads a token's tile puts on sublanes in whole sublane groups (the
    value heads; the per-head kernel tiles by the ``key_heads`` under them
    too) and a chunk the tiling admits (a multiple of
    8 up to 128; the per-head kernels up to ``HEAD_KERNEL_CHUNK``, at most
    ``HEAD_KERNEL_ROWS`` tokens x heads a chunk). The XLA forms
    write their pairs and decayed keys to HBM, a few chunks at a time, and
    scan with ``_recurrence`` under a scan over groups; the kernel forms
    hold a chunk in VMEM and leave every chunk's operands ``[b, n, C, h,
    d]``, where the recurrence kernels scan them as they lie (module
    docstring)."""
    xla = "head" if per_head else "xla"
    if backend != "tpu" or jnp.dtype(dtype) != jnp.bfloat16:
        return xla
    if (d_k, d_v) != (KERNEL_WIDTH, KERNEL_WIDTH) or heads % 8:
        return xla
    if chunk % 8 or chunk > 128:
        return xla
    if not per_head:
        return "kernel"
    if key_heads % 8 or chunk > HEAD_KERNEL_CHUNK:
        return xla
    return "head_kernel" if chunk * heads <= HEAD_KERNEL_ROWS else xla


def _divisor(n: int, most: int) -> int:
    """The largest divisor of ``n`` that is at most ``most`` (at least 1)."""
    d = max(1, min(n, most))
    while n % d:
        d -= 1
    return d


def _recurrence(state, w, u_v, q_g, k_d, gamma, a_q, dtype):
    """The scan over the chunks of a group from ``state`` [b, h, d_k, d_v].
    Leading axis: the chunk; ``w``, ``q_g``, ``k_d`` [g, b, h, C, d_k] and
    ``a_q`` [g, b, h, C, C] in ``dtype``, ``u_v`` [g, b, h, C, d_v] and
    ``gamma`` [g, b, h, d_k] float32. Returns (the state after the group,
    o [g, b, h, C, d_v] float32)."""
    def product(spec, x, y):
        return jnp.einsum(spec, x, y.astype(dtype),
                          preferred_element_type=jnp.float32)

    def body(state, chunk):
        w_n, u_n, q_n, k_n, gamma_n, a_n = chunk
        u = u_n - product("bhck,bhkv->bhcv", w_n, state)
        o = (product("bhck,bhkv->bhcv", q_n, state)
             + product("bhij,bhjv->bhiv", a_n, u))
        state = (gamma_n[..., None] * state
                 + product("bhck,bhcv->bhkv", k_n, u))
        return state, o

    return lax.scan(body, state, (w, u_v, q_g, k_d, gamma, a_q))


def _scan_groups(one_group, state, xs, group: int):
    """The scan's two levels: ``xs`` [b, n, C, h, ...] in groups of ``group``
    chunks, ``one_group(state, xs of a group [b, group, h, C, ...]) ->
    (state, o [group, b, h, C, d_v])`` one at a time from a zero state of
    shape ``state`` and recomputed in the backward pass, so that the scan
    keeps one state a group and a group's operands are alive for that group
    alone. Returns o [b, n C, h, d_v]."""
    b, n, chunk, h = xs[-1].shape[:4]

    def grouped(x):              # [b, n, C, h, ...] -> [n / group, b,
        x = x.reshape(b, n // group, group, *x.shape[2:])      # group,
        return jnp.moveaxis(x, 1, 0).swapaxes(3, 4)       # h, C, ...]

    xs = tuple(grouped(x) for x in xs)
    o = lax.scan(jax.checkpoint(one_group), jnp.zeros(state, jnp.float32),
                 xs)[1]
    # [n / group, group, b, h, C, d_v] -> [b, n C, h, d_v]
    return o.transpose(2, 0, 1, 4, 3, 5).reshape(b, n * chunk, h, -1)


def kda_attention(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16,
                  dtype=jnp.bfloat16):
    """``o`` [b, s, h, d_v] float32 of the recurrence in the module
    docstring. q, k [b, s, h_k, d_k] (the caller normalises and scales them;
    ``h_k`` divides h), v [b, s, h, d_v], g the log-decay (<= 0), [b, s, h,
    d_k] a channel or [b, s, h] a head, beta [b, s, h]. ``chunk`` need not
    divide s (zero tokens are appended and dropped); ``sub`` divides
    ``chunk`` (``sub = chunk``: all pairs one by one; the per-head form has
    no use for it)."""
    if chunk % sub:
        raise ValueError(f"sub ({sub}) must divide chunk ({chunk})")
    h, per_head = v.shape[2], g.ndim == 3
    if not (q.shape == k.shape and q.shape[:2] == v.shape[:2]
            and h % q.shape[2] == 0 and beta.shape == v.shape[:3]
            and g.shape == v.shape[:3] + (() if per_head else q.shape[3:])):
        raise ValueError("kda_attention: q, k [b, s, h_k, d_k] (h_k a "
                         "divisor of h), v [b, s, h, d_v], g [b, s, h, d_k] "
                         "or [b, s, h], beta [b, s, h]; got "
                         f"{q.shape}, {k.shape}, {v.shape}, {g.shape}, "
                         f"{beta.shape}")
    s = q.shape[1]
    metrics.inc_counter(SCAN_SITES)
    form = kda_form(jax.default_backend(), h, q.shape[3], v.shape[3], dtype,
                    chunk, per_head, q.shape[2])
    kernel = form in ("kernel", "head_kernel")
    if kernel:
        # imported here: a process that never reaches this line (every
        # other model, any CPU run) pays for no kernel library
        # (tests/test_import_footprint.py)
        from byteps_tpu.ops.kda_recurrence import recurrence

        if per_head:
            from byteps_tpu.ops.gdn_chunk import head_operands

            metrics.inc_counter(HEAD_KERNEL_SITES)
        else:
            from byteps_tpu.ops.kda_chunk import chunk_operands

            metrics.inc_counter(KERNEL_SITES)
        metrics.inc_counter(RECURRENCE_KERNEL_SITES)
    if per_head:
        metrics.inc_counter(HEAD_SITES)
    elif q.shape[2] != h:
        # one decay a channel knows no groups: a gather XLA fuses
        q, k = (jnp.repeat(x, h // q.shape[2], axis=2) for x in (q, k))
    prep_scope, scan_scope = ((GDN_PREP_SCOPE, GDN_SCAN_SCOPE) if per_head
                              else (PREP_SCOPE, SCAN_SCOPE))
    f32 = jnp.float32
    tokens = tuple(chunked(x.astype(f32), chunk) for x in (q, k, v, beta))
    if not kernel:
        with jax.named_scope(prep_scope):
            G = chunk_log_decay(g, chunk)               # [b, n, C, h, d_k]
    with jax.named_scope(scan_scope):
        b, n, _, _, d_k = tokens[0].shape
        state = (b, h, d_k, v.shape[-1])            # float32, S_0 = 0
        if kernel:
            # Every chunk's operands in one call (the kernel cumulates a
            # chunk's decay itself): [b, n, C, h, d], a token a [h, d] tile.
            # The recurrence kernels read them where they lie, all chunks in
            # one call, and keep a state every 16 chunks for their backward
            # pass themselves: nothing is sliced, laid out anew or copied on
            # its way to a group (PERF.md section 6, PR 54 and PR 59). They
            # round the pairs [.., C, h, C] to ``dtype`` ahead of the call
            # (the per-head kernels write them so): a row of 32 fills a
            # quarter of its lanes, so in float32 they are 134 MB a layer at
            # 256 chunks, and their gradient as much.
            zero = jnp.zeros(state, f32)
            raw = chunked(g.astype(f32), chunk)
            operands = (head_operands(*tokens, raw, dtype) if per_head
                        else chunk_operands(*tokens, raw, sub, dtype))
            o = recurrence(zero, *operands, dtype)[1]
            return o.reshape(b, n * chunk, h, -1)[:, :s]
        # Two levels: groups of chunks, one at a time and recomputed in the
        # backward pass, and the chunks of a group: the scan keeps one state
        # a group and a group's states while it is differentiated. A group's
        # operands are alive for that group alone. The pairs of a sub-chunk
        # are a [.., sub, sub, d_k] tensor, which the backward pass writes
        # out: a group is as many chunks as keep that tensor under 2^26
        # entries (256 MB). One decay a head has no such tensor (a chunk's
        # largest are [C, C] a head): its group is ``HEAD_GROUP`` chunks.
        group = _divisor(n, HEAD_GROUP if per_head else
                         2 ** 26 // (b * h * chunk * sub * d_k))
        operands = _head_operands if per_head else partial(
            _chunk_operands, sub=sub)

        def one_group(state, xs):
            return _recurrence(state, *(
                jnp.moveaxis(x, 1, 0) for x in operands(*xs, dtype=dtype)),
                dtype)

        return _scan_groups(one_group, state, (*tokens, G), group)[:, :s]


def _ssd_group(state, xs, dtype):
    """One group of chunks of the state-space scan from ``state`` [b, h, n,
    p]: c, b [b, group, h_k, C, n], u [b, group, h, C, p] (the write,
    ``dt x``) and G [b, group, h, C] float32 -> (the state after the group,
    y [group, b, h, C, p] float32). A token's output is its chunk's pairs
    over the chunk's writes plus the chunk's first state read through its
    own decay; every chunk's share of the next state is known before any
    state is, so that what runs chunk after chunk is ``S <- e^{G_C} S + Z``
    and nothing else. A group's ``B`` and ``C`` are multiplied once a key
    head, never repeated over the heads they serve."""
    c, b, u, G = xs
    lead, h = G.shape[:2], G.shape[2]
    groups = h // c.shape[2]
    size = G.shape[-1]
    i, j = jnp.arange(size)[:, None], jnp.arange(size)[None, :]

    def product(spec, x, y):
        return jnp.einsum(spec, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=jnp.float32)

    def by_key_head(x):     # [b, group, h, ...] -> [b, group, h_k, r, ...]
        return x.reshape(*lead, h // groups, groups, *x.shape[3:])

    pairs = (jnp.repeat(product("...id,...jd->...ij", c, b), groups, 2)
             * _pair_decay(G, i, j))                   # [b, group, h, C, C]
    total = G[..., -1:]                             # a chunk's whole decay
    z = product("bgkcn,bgkrcp->bgkrnp", b,
                by_key_head(u * jnp.exp(total - G)[..., None]))
    state, first = lax.scan(
        lambda state, chunk: (chunk[0] * state + chunk[1], state), state,
        (jnp.moveaxis(jnp.exp(total)[..., None], 1, 0),
         jnp.moveaxis(z.reshape(*lead, h, *z.shape[-2:]), 1, 0)))
    read = product("bgkcn,bgkrnp->bgkrcp", c, by_key_head(
        jnp.moveaxis(first, 0, 1))).reshape(u.shape)
    y = product("...ij,...jp->...ip", pairs, u) + jnp.exp(G)[..., None] * read
    return state, jnp.moveaxis(y, 1, 0)


def ssd_form(backend: str, dtype, state: int, channels: int, heads: int,
             groups: int, chunk: int) -> str:
    """``"kernel"`` or ``"xla"``: how the state-space scan runs at these
    shapes. One algorithm; the XLA form (``_ssd_group`` under
    ``_scan_groups``) for every backend, dtype and shape, and the kernels of
    ``byteps_tpu.ops.ssd_scan`` under one rule of the shapes: a ``tpu``
    backend, bf16 operands, a state of ``KERNEL_WIDTH`` entries (``B`` and
    ``C`` of a group are a row of lanes), heads of half that many channels
    (two heads a row of lanes), the heads of a group a multiple of 8 (the
    group's log-decay with tokens on lanes is whole sublane groups) and
    chunks of ``KERNEL_WIDTH`` tokens (a chunk's masks are one tile a
    head)."""
    if backend != "tpu" or jnp.dtype(dtype) != jnp.bfloat16:
        return "xla"
    if (state, channels, chunk) != (KERNEL_WIDTH, KERNEL_WIDTH // 2,
                                    KERNEL_WIDTH):
        return "xla"
    if groups < 1 or heads % groups or (heads // groups) % 8:
        return "xla"
    return "kernel"


def ssd_scan_channels(mixed, g, dt, *, heads: int, groups: int, state: int,
                      chunk: int = 128, dtype=jnp.bfloat16):
    """``ssd_scan`` over operands as a Mamba-2 mixer's convolution leaves
    them, a token's channels on lanes: ``mixed`` [b, s, heads p + 2 groups
    n] = ``[x | B | C]`` (head h of ``x`` on channels ``p h .. p h + p -
    1``, group j of ``B`` and of ``C`` on ``n j .. n j + n - 1`` of theirs),
    g and dt [b, s, heads] -> ``(y, x)``, ``y`` [b, s, heads p] float32 and
    ``x`` = ``mixed[..., :heads p]`` for a caller that uses it beside ``y``
    (``ops/ssd_scan.py::ssd_scan_kernel`` has the reason). Where
    ``ssd_form`` says ``"kernel"`` nothing is laid out anew on the way in or
    out; the XLA form cuts ``mixed`` into heads and groups as its caller
    used to."""
    b_, s, channels = mixed.shape
    inner = channels - 2 * groups * state
    if (inner <= 0 or inner % heads
            or not g.shape == dt.shape == (b_, s, heads)):
        raise ValueError("ssd_scan_channels: mixed [b, s, heads p + 2 groups "
                         f"n], g, dt [b, s, heads]; got {mixed.shape}, "
                         f"{g.shape}, {dt.shape} at {heads} heads, {groups} "
                         f"groups of {state}")
    if ssd_form(jax.default_backend(), dtype, state, inner // heads, heads,
                groups, chunk) != "kernel":
        c, b = (mixed[..., inner + i * groups * state:
                      inner + (i + 1) * groups * state].reshape(
                          b_, s, groups, state) for i in (1, 0))
        x = mixed[..., :inner]
        return ssd_scan(c, b, x.reshape(b_, s, heads, -1), g, dt,
                        chunk=chunk, dtype=dtype).reshape(b_, s, inner), x
    # imported here: a process that never reaches this line (every other
    # model, any CPU run) pays for no kernel library
    # (tests/test_import_footprint.py)
    from byteps_tpu.ops.ssd_scan import ssd_scan_kernel

    metrics.inc_counter(SSM_SCAN_SITES)
    metrics.inc_counter(SSM_SCAN_KERNEL_SITES)
    f32 = jnp.float32
    with jax.named_scope(SSM_PREP_SCOPE):
        G = chunk_log_decay(g, chunk)                   # [b, n, C, h]
        G = G.reshape(b_, -1, heads)
    with jax.named_scope(SSM_SCAN_SCOPE):
        after = [(0, 0), (0, G.shape[1] - s), (0, 0)]   # zero tokens
        y, x = ssd_scan_kernel(
            jnp.pad(mixed.astype(f32), after), G,
            jnp.pad(dt.astype(f32), after), groups=groups, state=state,
            dtype=dtype)
        return y[:, :s], x[:, :s]


def ssd_scan(c, b, x, g, dt, *, chunk: int = 128, dtype=jnp.bfloat16):
    """``y`` [b, s, h, p] float32 of the selective state-space recurrence
    (Mamba-2's SSD): per head a float32 state ``S`` [n, p] from zero,

        S_t = e^{g_t} S_{t-1} + B_t (dt_t x_t)^T,        y_t = S_t^T C_t.

    c, b [b, s, h_k, n] (``h_k`` groups, a divisor of h: group j serves
    heads ``j h / h_k .. (j + 1) h / h_k - 1``), x [b, s, h, p], g the
    log-decay (<= 0) and dt the step, [b, s, h]. ``chunk`` need not divide
    s. The module's chunk algebra with one decay a head and the triangular
    system gone: ``U`` is ``dt x`` itself and ``W`` zero (module
    docstring). Where ``ssd_form`` says ``"kernel"`` the operands are laid
    out ``[s, channels]`` for ``ssd_scan_channels``, which a caller that
    has them so calls itself."""
    h = x.shape[2]
    if not (c.shape == b.shape and c.shape[:2] == x.shape[:2]
            and h % c.shape[2] == 0 and g.shape == dt.shape == x.shape[:3]):
        raise ValueError("ssd_scan: c, b [b, s, h_k, n] (h_k a divisor of "
                         "h), x [b, s, h, p], g, dt [b, s, h]; got "
                         f"{c.shape}, {b.shape}, {x.shape}, {g.shape}, "
                         f"{dt.shape}")
    s = x.shape[1]
    if ssd_form(jax.default_backend(), dtype, c.shape[3], x.shape[3], h,
                c.shape[2], chunk) == "kernel":
        return ssd_scan_channels(
            jnp.concatenate([t.reshape(*t.shape[:2], -1) for t in (x, b, c)],
                            axis=-1),
            g, dt, heads=h, groups=c.shape[2], state=c.shape[3], chunk=chunk,
            dtype=dtype)[0].reshape(x.shape)
    metrics.inc_counter(SSM_SCAN_SITES)
    f32 = jnp.float32
    with jax.named_scope(SSM_PREP_SCOPE):
        G = chunk_log_decay(g, chunk)                   # [b, n, C, h]
    with jax.named_scope(SSM_SCAN_SCOPE):
        u = dt.astype(f32)[..., None] * x.astype(f32)
        tokens = tuple(chunked(t.astype(f32), chunk) for t in (c, b, u))
        return _scan_groups(
            partial(_ssd_group, dtype=dtype),
            (x.shape[0], h, c.shape[3], x.shape[3]), (*tokens, G),
            _divisor(G.shape[1], SSD_GROUP))[:, :s]


def sel_chunk_log_decay(dt: jax.Array, a: jax.Array,
                        chunk: int = 0) -> jax.Array:
    """[b, ceil(s / chunk), channels] float32: the log-decay a chunk of
    ``selective_scan`` lays on its channels' fastest state entry, ``min_n
    a[c, n]`` times the chunk's summed ``dt`` (``chunk`` 0: ``SEL_CHUNK``).
    The scan multiplies token by token and never forms this number: the
    gauge says how far a state decays between two kept states."""
    return (chunked(dt.astype(jnp.float32), chunk or SEL_CHUNK).sum(2)
            * a.astype(jnp.float32).min(-1))


def selective_scan(x, dt, a, b, c, *, chunk: int = 0):
    """``y`` [b, s, channels] float32 of Mamba-1's selective state-space
    recurrence (module docstring, "one decay a channel and state entry"):
    per channel a float32 state ``S`` [n] from zero,

        S_t[c, n] = exp(dt_t[c] a[c, n]) S_{t-1}[c, n] + dt_t[c] b_t[n] x_t[c]
        y_t[c] = sum_n c_t[n] S_t[c, n]

    without the skip ``D x``: the caller adds it. x, dt [b, s, channels]
    (dt the step after its softplus), a [channels, n] (< 0), b, c [b, s, n].
    ``chunk`` (0: ``SEL_CHUNK``) need not divide s: zero tokens (dt 0: a
    decay of 1 and no write) are appended and dropped."""
    if not (x.shape == dt.shape and b.shape == c.shape
            and b.shape[:2] == x.shape[:2]
            and a.shape == (x.shape[2], b.shape[2])):
        raise ValueError("selective_scan: x, dt [b, s, channels], a "
                         "[channels, n], b, c [b, s, n]; got "
                         f"{x.shape}, {dt.shape}, {a.shape}, {b.shape}, "
                         f"{c.shape}")
    metrics.inc_counter(SEL_SCAN_SITES)
    f32 = jnp.float32
    size = chunk or SEL_CHUNK
    with jax.named_scope(SEL_SCAN_SCOPE):
        # [n_chunks, C, b, ...]: both scans' leading axes
        dt = dt.astype(f32)
        tokens = tuple(jnp.moveaxis(chunked(t.astype(f32), size), 0, 2)
                       for t in (dt, dt * x.astype(f32), b, c))
        y = _sel_chunks(a.astype(f32).T, tokens)  # [n_chunks, C, b, channels]
        return jnp.moveaxis(y.reshape(-1, *y.shape[2:]), 0, 1)[:, :x.shape[1]]


def _sel_chunk(a_t, state, tokens):
    """One chunk from ``state`` [b, n, channels] — state entries on
    sublanes, channels on lanes — under ``a_t`` [n, channels]: a ``lax.scan``
    over the chunk's tokens, each its dt, u = dt x [b, channels] and b, c
    [b, n]. Returns (the state after the chunk, y [C, b, channels])."""
    def token(state, inputs):
        dt_t, u_t, b_t, c_t = inputs
        state = (jnp.exp(dt_t[:, None, :] * a_t) * state
                 + b_t[:, :, None] * u_t[:, None, :])
        return state, (c_t[:, :, None] * state).sum(1)

    return lax.scan(token, state, tokens)


def _sel_forward(a_t, tokens):
    """(y [n_chunks, C, b, channels], every chunk's first state [n_chunks,
    b, n, channels]): the state alone is kept from chunk to chunk."""
    dt = tokens[0]

    def chunk(state, chunk_tokens):
        after, y = _sel_chunk(a_t, state, chunk_tokens)
        return after, (y, state)

    return lax.scan(chunk, jnp.zeros((dt.shape[2], *a_t.shape), jnp.float32),
                    tokens)[1]


@jax.custom_vjp
def _sel_chunks(a_t, tokens):
    return _sel_forward(a_t, tokens)[0]


def _sel_chunks_fwd(a_t, tokens):
    y, first = _sel_forward(a_t, tokens)
    # named, so that a caller's recomputation can keep them beside ``y`` and
    # then has no forward scan left to run again
    return y, (a_t, tokens, checkpoint_name(first, SEL_STATES))


def _sel_chunks_bwd(res, dy):
    """Chunk by chunk from the last: a chunk is computed again from its kept
    first state and differentiated (``jax.vjp``): its C states and decays are
    the most that is alive of the [s, n, channels] history."""
    a_t, tokens, first = res

    def chunk(carry, inputs):
        d_after, d_a = carry
        state, chunk_tokens, dy_c = inputs
        d_a_c, d_state, d_tokens = jax.vjp(
            _sel_chunk, a_t, state, chunk_tokens)[1]((d_after, dy_c))
        return (d_state, d_a + d_a_c), d_tokens

    (_, d_a), d_tokens = lax.scan(
        chunk, (jnp.zeros_like(first[0]), jnp.zeros_like(a_t)),
        (first, tokens, dy), reverse=True)
    return d_a, d_tokens


_sel_chunks.defvjp(_sel_chunks_fwd, _sel_chunks_bwd)


def publish_kda_stats(kda_stats,
                      gauge: str = "bps_kda_min_chunk_log_decay") -> dict:
    """The ``"kda_stats"`` collection of a model applied with it mutable
    (every leaf the most negative cumulated log-decay of a chunk, one per
    KDA layer) to ``monitor/metrics.py``: gauge
    ``bps_kda_min_chunk_log_decay``, the least over the layers — under -87.3
    ``exp`` of it is 0 in float32, and a form that exponentiated its
    negation would have overflowed. ``gauge``: another name for the same
    reading of another scan (``bps_ssm_min_chunk_log_decay`` of a
    state-space model's ``"ssm_stats"``). Returns what it published."""
    leaves = [float(x) for x in jax.tree_util.tree_leaves(kda_stats)]
    if not leaves or not all(map(math.isfinite, leaves)):
        return {}
    metrics.set_gauge(gauge, min(leaves))
    return {gauge: min(leaves)}
