"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context scope beyond reference parity (the reference never touches
model internals — SURVEY.md §5 "Long-context / sequence parallelism:
absent"); this module is the TPU-native long-sequence answer the task
brief makes first-class.

Design (blockwise ring attention, Liu et al.'s RingAttention shape): the
sequence is sharded over a mesh axis (``sp``). Each device holds one
Q/K/V block; K/V blocks rotate around the ring via ``lax.ppermute`` while
each device accumulates attention of its local Q against every block with
an online (streaming) softmax — numerically identical to full attention,
memory O(S/n) per device. The ppermute for step i+1 is data-independent
of step i's matmuls, so XLA's latency-hiding scheduler overlaps the ICI
transfer with the block compute — the same comm/compute overlap the
reference engineered with its pipeline threads (core_loops.cc), here
falling out of the dataflow graph.

All functions are per-device code: call inside ``jax.shard_map`` over a
mesh with the named sequence axis. Layout [batch, seq, heads, head_dim];
block matmuls run on the MXU in the input dtype, accumulation in f32.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from byteps_tpu.jax._compat import axis_size as _axis_size
from byteps_tpu.monitor import metrics


def _big_neg(dtype) -> float:
    return float(jnp.finfo(dtype).min) / 2


def _block_attn(q, k, v, m, l, o, q_pos, k_pos, causal, scale):
    """One blockwise attention update with streaming-softmax state.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; m/l: [B, H, Sq]; o: [B, Sq, H, D].
    Everything but the matmul inputs is float32.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None, :, :], s, _big_neg(jnp.float32))
    m_new = jnp.maximum(m, s.max(axis=-1))
    # m_new is finite (>= _big_neg/1) so exp never sees inf-inf.
    p = jnp.exp(s - m_new[..., None])
    correction = jnp.exp(m - m_new)
    l_new = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    o_new = o * correction.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    axis: str = "sp",
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on mesh axis ``axis``.

    Per-device code (use under shard_map). ``q``/``k``/``v`` are the local
    sequence blocks, shape [batch, seq_local, heads, head_dim]; the global
    sequence length is seq_local * axis_size. Returns the local block of
    the attention output, same shape/dtype as ``q``.

    ``causal`` masks by *global* position, so the result equals full causal
    attention on the gathered sequence.
    """
    n = _axis_size(axis)
    my = lax.axis_index(axis)
    b, s_q, h, d = q.shape
    if scale is None:
        scale = 1.0 / (d ** 0.5)

    if n == 1:
        return _single_device_attention(q, k, v, causal=causal, scale=scale)

    m0 = jnp.full((b, h, s_q), _big_neg(jnp.float32), jnp.float32)
    l0 = jnp.zeros((b, h, s_q), jnp.float32)
    o0 = jnp.zeros((b, s_q, h, d), jnp.float32)
    q_pos = my * s_q + jnp.arange(s_q)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, i):
        kv, m, l, o = carry
        k_blk, v_blk = kv
        # The block now held originated on device (my - i) mod n.
        src = (my - i) % n
        k_pos = src * s_q + jnp.arange(k_blk.shape[1])
        # Launch the rotation first: it does not depend on this step's
        # matmuls, so the ICI permute overlaps the block compute.
        kv_next = jax.tree_util.tree_map(
            lambda x: lax.ppermute(x, axis, perm), kv)
        m, l, o = _block_attn(q, k_blk, v_blk, m, l, o,
                              q_pos, k_pos, causal, scale)
        return (kv_next, m, l, o), None

    # n-1 rotating steps in a scan, then the last block unrolled with no
    # trailing ppermute (its result would be discarded — one whole K/V
    # block of ICI traffic saved per layer per step).
    (kv_last, m, l, o), _ = lax.scan(
        step, ((k, v), m0, l0, o0), jnp.arange(n - 1))
    src = (my - (n - 1)) % n
    k_pos = src * s_q + jnp.arange(kv_last[0].shape[1])
    m, l, o = _block_attn(q, kv_last[0], kv_last[1], m, l, o,
                          q_pos, k_pos, causal, scale)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _single_device_attention(q, k, v, *, causal: bool, scale: float,
                             window: Optional[int] = None):
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        mask = jnp.arange(s_q)[:, None] >= jnp.arange(s_k)[None, :]
        if window is not None:
            mask &= (jnp.arange(s_q)[:, None] - jnp.arange(s_k)[None, :]
                     < window)
        s = jnp.where(mask[None, None], s, _big_neg(jnp.float32))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(p.dtype),
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


# The two forms of exact attention over an unsharded sequence, as a device
# trace names them (``jax.named_scope``) and as the metrics endpoint counts
# their call sites at trace time.
KERNEL_SCOPE, XLA_SCOPE = "bps.attn.kernel", "bps.attn.xla"
KERNEL_SITES = "bps_attention_kernel_sites_total"
XLA_SITES = "bps_attention_xla_sites_total"
# ... and the kernel sites whose backward pass is the one fused kernel
# (``ops/flash_attention.py::backward_form`` of the site's shapes)
FUSED_BACKWARD_SITES = "bps_attention_fused_backward_sites_total"
# ... and the blocks the kernel sites' grids compute, over batch and heads,
# beside those of them that run with no mask formed: wholly under the
# diagonal, inside the window, no padding (``flash_attention.block_census``)
LIVE_BLOCKS = "bps_attention_live_blocks_total"
INTERIOR_BLOCKS = "bps_attention_interior_blocks_total"

# The shortest sequence and the head widths (queries and keys, values) at
# which the Pallas kernel was measured against the XLA form on a TPU v5e,
# bf16, forward + backward, and won (PERF.md section 3, kernels; PR 36):
# causal 16 x 128 at s 512 / 1024 / 2048 / 4096 1.20 / 1.56 / 2.25 / 3.16 ms
# against 2.03 / 3.85 / 7.11 / 13.77, causal 12 x 64 at s 512 / 1024 / 2048
# 1.73 / 2.09 / 3.05 against 2.91 / 5.57 / 10.29; 192 / 128 in PR 39; 256 /
# 256 in PR 50, where the XLA form's float32 scores of 16 heads at s 8192
# are 4.3 GB and no step.
KERNEL_MIN_SEQ = 512
KERNEL_HEAD_DIMS = ((64, 64), (128, 128), (192, 128), (256, 256))


# A windowed call site, and the (query, key) pairs its form computes and
# the band needs, summed over batch and heads at trace time: the kernel
# form computes every block the band touches, the XLA form the square.
WINDOW_SITES = "bps_attention_window_sites_total"
WINDOW_WALKED = "bps_attention_window_walked_pairs"
WINDOW_NEEDED = "bps_attention_window_needed_pairs"


def attention_form(backend: str, s_q: int, s_k: int, head_dim: int,
                   causal: bool, dtype, value_dim: int = 0,
                   window: Optional[int] = None) -> str:
    """``"kernel"`` or ``"xla"``: how ``full_attention`` computes operands of
    these shapes (``value_dim``: v's width where it is not q's and k's). One
    algorithm, two forms: the XLA form writes float32 ``[batch, heads, s_q,
    s_k]`` scores to HBM, the kernel (TPU only) keeps a block in VMEM. A
    ``window`` does not move the rule: the kernel's grid then walks the
    band alone, the XLA form masks the square."""
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if backend != "tpu" or jnp.dtype(dtype) != jnp.bfloat16:
        return "xla"
    widths = (head_dim, value_dim or head_dim)
    if widths not in KERNEL_HEAD_DIMS or min(s_q, s_k) < KERNEL_MIN_SEQ:
        return "xla"
    return "kernel" if causal else "xla"


def _count_window(q, window: int, walked: int):
    """``walked``: the pairs the form computes for one head of one
    sequence; needed are the band's own, ``sum_q min(q + 1, window)``."""
    short = min(window, q.shape[1])
    needed = short * (short + 1) // 2 + (q.shape[1] - short) * window
    metrics.inc_counter(WINDOW_SITES)
    metrics.inc_counter(WINDOW_WALKED, q.shape[0] * q.shape[2] * walked)
    metrics.inc_counter(WINDOW_NEEDED, q.shape[0] * q.shape[2] * needed)


def full_attention(q, k, v, *, causal: bool = False,
                   scale: Optional[float] = None,
                   window: Optional[int] = None):
    """Exact softmax attention over an unsharded sequence: float32 logits
    and softmax statistics, both products on the operands' own dtype. On a
    TPU, for the shapes ``attention_form`` names, the Pallas kernel of
    ``byteps_tpu.ops.flash_attention`` (blockwise, the scores never leave
    VMEM); everywhere else the two einsums around a softmax that XLA
    fuses as it sees fit. ``window`` (causal only): a query sees the last
    ``window`` keys, itself among them. ``k`` and ``v`` may have fewer heads
    than ``q``, a divisor: query head i reads key head ``i // groups``."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    groups = q.shape[2] // k.shape[2]
    if groups * k.shape[2] != q.shape[2] or v.shape[2] != k.shape[2]:
        raise ValueError(f"{q.shape[2]} query heads over {k.shape[2]} key "
                         f"and {v.shape[2]} value heads")
    form = attention_form(jax.default_backend(), q.shape[1], k.shape[1],
                          q.shape[-1], causal, q.dtype, v.shape[-1], window)
    if form == "kernel":
        # imported here: a process that never reaches this line (BERT's
        # s128, any CPU run) pays for no kernel library
        # (tests/test_import_footprint.py)
        from byteps_tpu.ops.flash_attention import (
            backward_form, block_census, flash_attention,
            window_walked_pairs)

        metrics.inc_counter(KERNEL_SITES)
        live, interior = block_census(q.shape[1], k.shape[1], q.shape[-1],
                                      window)
        metrics.inc_counter(LIVE_BLOCKS, q.shape[0] * q.shape[2] * live)
        metrics.inc_counter(INTERIOR_BLOCKS,
                            q.shape[0] * q.shape[2] * interior)
        if backward_form(q.shape[1], k.shape[1], q.shape[-1], v.shape[-1],
                         groups, window, q.dtype.itemsize) == "fused":
            metrics.inc_counter(FUSED_BACKWARD_SITES)
        if window is not None:
            # the kernel's own count of the blocks its grids compute
            _count_window(q, window, window_walked_pairs(
                q.shape[1], k.shape[1], q.shape[-1], window))
        with jax.named_scope(KERNEL_SCOPE):
            return flash_attention(q, k, v, causal, scale, window=window)
    metrics.inc_counter(XLA_SITES)
    if window is not None:
        _count_window(q, window, q.shape[1] * k.shape[1])   # the square
    with jax.named_scope(XLA_SCOPE):
        if groups > 1:
            # the XLA form knows no groups: a gather it fuses
            k, v = (jnp.repeat(x, groups, axis=2) for x in (k, v))
        return _single_device_attention(q, k, v, causal=causal, scale=scale,
                                        window=window)


@partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _ring_sharded_impl(q, k, v, mesh, axis, causal, scale):
    from jax.sharding import PartitionSpec as P

    from byteps_tpu.jax._compat import shard_map as _shard_map

    spec = P(None, axis, None, None)
    run = _shard_map(
        lambda ql, kl, vl: ring_attention(ql, kl, vl, axis=axis,
                                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return run(q, k, v)


def ring_attention_sharded(q, k, v, mesh, *, axis: str = "sp",
                           causal: bool = False,
                           scale: Optional[float] = None):
    """Convenience wrapper: global [B, S, H, D] arrays in, jitted
    shard_map'd ring attention over ``mesh``'s ``axis`` out. The jit cache
    is keyed on (mesh, axis, causal, scale) — loops don't recompile."""
    return _ring_sharded_impl(q, k, v, mesh, axis, causal, scale)
