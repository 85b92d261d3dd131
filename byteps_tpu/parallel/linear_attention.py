"""Gated delta-rule linear attention with a per-channel decay (KDA: Kimi
Linear, Moonshot AI 2025, arXiv 2510.26692; the public ``fla`` layer
``KimiDeltaAttention``) as a chunked scan.

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
and accumulate in float32. The backward pass is ``jax.grad`` through all of
it, a group of chunks recomputed at a time (``jax.checkpoint``): the scan
keeps one state a group. A hand-written backward of the recurrence was
measured against it and lost (PERF.md section 6, PR 39).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.monitor import metrics

# As a device trace names the op's two parts (``jax.named_scope``), and the
# counter of its call sites at trace time.
PREP_SCOPE, SCAN_SCOPE = "bps.kda.prep", "bps.kda.scan"
SCAN_SITES = "bps_kda_scan_sites_total"

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
    """[b, n, chunk, h, d_k] float32: ``g`` [b, s, h, d_k] cumulated inside
    each chunk of ``chunk`` tokens."""
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

    def solved(x):
        return jnp.einsum("...ij,...jd->...id", t.astype(dtype),
                          (beta[..., None] * x).astype(dtype),
                          preferred_element_type=jnp.float32)

    # what only ever is a matmul operand is kept in ``dtype``
    return (solved(k * jnp.exp(G)).astype(dtype), solved(v),
            (q * jnp.exp(G)).astype(dtype),
            (k * jnp.exp(total - G)).astype(dtype),
            jnp.exp(total[..., 0, :]), a_q.astype(dtype))


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


def kda_attention(q, k, v, g, beta, *, chunk: int = 64, sub: int = 16,
                  dtype=jnp.bfloat16):
    """``o`` [b, s, h, d_v] float32 of the recurrence in the module
    docstring. q, k [b, s, h, d_k] (the caller normalises and scales them),
    v [b, s, h, d_v], g [b, s, h, d_k] the log-decay (<= 0), beta [b, s, h].
    ``chunk`` need not divide s (zero tokens are appended and dropped);
    ``sub`` divides ``chunk`` (``sub = chunk``: all pairs one by one)."""
    if chunk % sub:
        raise ValueError(f"sub ({sub}) must divide chunk ({chunk})")
    if not (q.shape == k.shape == g.shape and beta.shape == q.shape[:3]
            and v.shape[:3] == q.shape[:3]):
        raise ValueError("kda_attention: q, k, g [b, s, h, d_k], v [b, s, h, "
                         f"d_v], beta [b, s, h]; got {q.shape}, {k.shape}, "
                         f"{g.shape}, {v.shape}, {beta.shape}")
    s = q.shape[1]
    metrics.inc_counter(SCAN_SITES)
    f32 = jnp.float32
    with jax.named_scope(PREP_SCOPE):
        G = chunk_log_decay(g, chunk)                   # [b, n, C, h, d_k]
    with jax.named_scope(SCAN_SCOPE):
        b, n, _, h, d_k = G.shape
        # Two levels: groups of chunks, one at a time and recomputed in the
        # backward pass, and the chunks of a group. A group's operands (the
        # pairs of a sub-chunk are a [.., sub, sub, d_k] tensor, which the
        # backward pass writes out) are alive for that group alone; a group
        # is as many chunks as keep that tensor under 2^26 entries (256 MB).
        group = max(1, min(n, 2 ** 26 // (b * h * chunk * sub * d_k)))
        while n % group:
            group -= 1

        def grouped(x):              # [b, n, C, h, ...] -> [n / group, b,
            x = x.reshape(b, n // group, group, *x.shape[2:])    # group, h,
            return jnp.moveaxis(x, 1, 0).swapaxes(3, 4)          # C, ...]

        @jax.checkpoint
        def one_group(state, xs):
            operands = _chunk_operands(*xs, sub, dtype)  # [b, group, h, ...]
            return _recurrence(
                state, *(jnp.moveaxis(x, 1, 0) for x in operands), dtype)

        state = jnp.zeros((b, h, d_k, v.shape[-1]), f32)
        o = lax.scan(one_group, state, tuple(grouped(x) for x in (
            *(chunked(x.astype(f32), chunk) for x in (q, k, v, beta)),
            G)))[1]
        # [n / group, group, b, h, C, d_v] -> [b, s, h, d_v]
        return o.transpose(2, 0, 1, 4, 3, 5).reshape(
            b, n * chunk, h, -1)[:, :s]


def publish_kda_stats(kda_stats) -> dict:
    """The ``"kda_stats"`` collection of a model applied with it mutable
    (every leaf the most negative cumulated log-decay of a chunk, one per
    KDA layer) to ``monitor/metrics.py``: gauge
    ``bps_kda_min_chunk_log_decay``, the least over the layers — under -87.3
    ``exp`` of it is 0 in float32, and a form that exponentiated its
    negation would have overflowed. Returns what it published."""
    leaves = [float(x) for x in jax.tree_util.tree_leaves(kda_stats)]
    if not leaves or not all(map(math.isfinite, leaves)):
        return {}
    out = {"bps_kda_min_chunk_log_decay": min(leaves)}
    metrics.set_gauge("bps_kda_min_chunk_log_decay",
                      out["bps_kda_min_chunk_log_decay"])
    return out
