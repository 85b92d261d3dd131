"""Learned sparse attention: a lightning indexer scores every earlier key
for every query, the ``topk`` best are selected exactly, attention runs over
the selected keys alone, and the indexer learns from a KL term of its own
(DeepSeek-V3.2's sparse attention; grouped-query attention underneath).

``sparse_attention`` works in query blocks of ``block`` rows, each
recomputed in the backward pass (``jax.checkpoint``); the blocks tile the
computation and do not change it. The selection is a boolean [queries,
keys] mask and the softmax runs over all keys of the block's span with the
unselected ones masked out: every product of the span is computed, those
of unselected keys included (``T / mean |S_t|`` times what the mathematics
needs). Chosen by measurement over gathering the selected keys, and the
spans over one loop and over unrolled blocks (PERF.md section 4).

**One computation, two forms of a block's attention** (``attend_form``, a
pure function of the backend, the operands' dtype and the shapes, as
``linear_attention.py::kda_form`` is for a KDA chunk; each counted at
trace time). The masked form, in XLA, is what every CPU run, float32
operands and any head width but 128 get: its [heads, block, keys] float32
scores — 0.54 GB at 32 heads x 512 x 8192 — go to HBM and come back a
dozen times a block, forward, recomputed and backward. On a ``tpu``
backend with bf16 operands, heads 128 wide and a block of a multiple of 32
queries, the kernels of ``byteps_tpu.ops.sparse_flash`` take the mask as
an int8 operand and hold a [block, tile] score in VMEM: forward, the
head-mean probabilities the indexer learns from, and one backward kernel.
Same arithmetic: bf16 operands, float32 accumulation, logits and sums. A
block keeps its [block, heads] logsumexp across the recomputation, so the
forward kernel runs once a step; the output is kept once, by whoever
reads it next (``sparse_flash.masked_attention``: how it is
differentiated). The kernel library is imported inside the branch that
takes the kernel form and nowhere else (``tests/test_import_footprint.py``).

The selection thresholds on each query's ``topk``-th highest score. That
one number a row is found without sorting: the float32 scores are read as
integers of the same order and the threshold's bits are fixed from the
top, two a pass, by counting how many scores reach each candidate — 16
compare-and-count passes over the block, exact for every input (PERF.md
section 4: a quarter to a ninth of ``lax.top_k``'s time at these widths).
The threshold and the room it leaves for ties, [block, 1] each, are named
and saved across the recomputation, so the backward pass rebuilds the
mask from them and searches nothing; the mask itself is never saved.

Gradient paths. The indexer's inputs are the caller's to detach; here the
attention probabilities that the indexer is trained towards are detached,
and the selection has no gradient. So the attention output carries no
gradient to ``index_*`` and ``index_loss`` none to ``q``, ``k``, ``v``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

INDEXER_SCOPE = "bps.dsa.indexer"  # index scores, the KL term, gradients
SELECT_SCOPE = "bps.dsa.select"    # the topk-th score and the mask
ATTEND_SCOPE = "bps.dsa.attend"    # scores, softmax, values, gradients
# counted at trace time: calls of ``sparse_attention``, and those of them
# whose blocks attend through the kernels
ATTEND_SITES = "bps_dsa_attend_sites_total"
KERNEL_SITES = "bps_dsa_kernel_sites_total"

# What the kernels of ``byteps_tpu.ops.sparse_flash`` were measured at on a
# TPU v5e and won (PERF.md section 6, PR 42): heads one row of lanes wide,
# bf16 operands. Their tiling admits a block of whole int8 sublane groups
# (a multiple of 32 queries) whose query heads of one key-value head fit
# the accumulators the kernels hold in VMEM (block x group up to 4096).
KERNEL_WIDTH = 128
KERNEL_ROWS = 4096


_INT_MIN = -2 ** 31
_DIGIT = 2          # bits of the threshold a pass fixes (PERF.md section 4)
_SAVED = "bps.dsa.kth"  # what of the selection outlives the forward pass


def _ordered(x):
    """float32 -> int32 with the floats' own order and equality: a
    negative float's bits count down from 0 by its magnitude, so -0.0 and
    +0.0 are one key and -inf the smallest (no NaN among the scores)."""
    bits = lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(bits < 0, _INT_MIN - bits, bits)


@partial(jax.jit, static_argnums=1)
def _kth_key(key, topk: int):
    """[rows, 1] int32: per row of ``key`` [rows, n >= topk] its
    ``topk``-th largest entry, exactly — the largest t that at least
    ``topk`` entries reach, its bits fixed from the top, ``_DIGIT`` a
    pass: a pass counts the entries that reach each candidate digit and
    keeps the highest digit that ``topk`` still reach. No sort, and the
    time does not turn on the data. One loop body under a jit of its own,
    so that a model of many layers traces the passes once a width and not
    once a pass, layer and span (unrolled in Python they added 6.6 s to
    each trace of the four-layer model: PERF.md section 6, PR 34)."""
    def fix(i, t):
        # int32 wraps on purpose: t starts at the smallest int32 and the
        # top digit carries it past zero
        shift = 32 - _DIGIT * (i + 1)
        reached = sum(
            ((key >= t + lax.shift_left(jnp.int32(digit), shift)).sum(
                axis=-1, keepdims=True, dtype=jnp.int32) >= topk
             ).astype(jnp.int32) for digit in range(1, 1 << _DIGIT))
        return t + lax.shift_left(reached, shift)

    return lax.fori_loop(0, 32 // _DIGIT, fix,
                         jnp.full((key.shape[0], 1), _INT_MIN, jnp.int32))


def _select(score, causal, topk: int):
    """[queries, keys] bool: per query its ``min(causal keys, topk)``
    causal keys of highest ``score``, ties to the earlier key: everything
    above the ``topk``-th value, and of the keys equal to it the earliest
    that still fit. The ``topk``-th value is found by ``_kth_key`` over
    the scores' ordered bits (a query with fewer causal keys finds -inf
    and keeps them all); it and the room left for ties are saved
    across the block's recomputation, so the backward pass rebuilds the
    mask from them and does not search again."""
    if score.shape[1] <= topk:
        return causal
    key = _ordered(jnp.where(causal, score, -jnp.inf))
    kth = checkpoint_name(_kth_key(key, topk), _SAVED)
    above, tied = key > kth, key == kth
    room = checkpoint_name(
        topk - above.sum(axis=-1, keepdims=True, dtype=jnp.int32), _SAVED)
    return (above | (tied & (jnp.cumsum(tied, axis=-1) <= room))) & causal


def attend_form(backend: str, dtype, head_dim: int, group: int,
                block: int) -> str:
    """``"kernel"`` or ``"masked"``: how a block of ``block`` queries, each
    key-value head under ``group`` query heads ``head_dim`` wide, attends
    over its selection. One computation, two forms: the masked form writes
    its [heads, block, keys] float32 scores to HBM and reads them back, the
    kernels (TPU only) hold a [block, tile] score in VMEM."""
    if backend != "tpu" or jnp.dtype(dtype) != jnp.bfloat16:
        return "masked"
    if head_dim != KERNEL_WIDTH or block % 32 or block * group > KERNEL_ROWS:
        return "masked"
    return "kernel"


def _block(q, index_q, index_w, first, k, v, index_k, *, topk, scale,
           kernel):
    """One block of queries at positions ``first..`` of one sequence over
    the keys 0..n-1. q [B, h, dh]; k, v [n, hk, dh]; index_q [B, hi, di];
    index_w [B, hi]; index_k [n, di]. ``kernel``: q [B, h * dh], the heads
    side by side, and k, v all keys of the sequence, of which the block
    reads those up to its last query. Returns (out [B, h, dh] float32 — the
    kernel form [B, h * dh] in q's dtype, yet to be ``renormalised``, and
    the logits' logsumexp [B, h], else ``None`` — the block's summed KL,
    its number of selected keys)."""
    rows = q.shape[0]
    n, (kv_heads, head_dim) = index_k.shape[0], k.shape[1:]
    heads = q.size // (rows * head_dim)
    hi = lax.Precision.HIGHEST
    causal = (jnp.arange(n)[None, :]
              <= (first + jnp.arange(rows))[:, None])
    with jax.named_scope(INDEXER_SCOPE):
        dots = jnp.einsum("qjd,sd->qjs", index_q, index_k, precision=hi)
        score = jnp.einsum("qjs,qj->qs", jax.nn.relu(dots), index_w,
                           precision=hi)                        # [B, n]
    with jax.named_scope(SELECT_SCOPE):
        keep = _select(lax.stop_gradient(score), causal, topk)
    if kernel:
        from byteps_tpu.ops.sparse_flash import masked_attention

        with jax.named_scope(ATTEND_SCOPE):
            out, lse, target = masked_attention(q, k, v, keep, first, scale)
    else:
        lse = None
        with jax.named_scope(ATTEND_SCOPE):
            grouped = q.reshape(rows, kv_heads, heads // kv_heads, head_dim)
            logits = jnp.einsum("qcgd,scd->cgqs", grouped, k,
                                preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(
                jnp.where(keep, logits, jnp.finfo(jnp.float32).min), axis=-1)
            out = jnp.einsum("cgqs,scd->qcgd", probs.astype(v.dtype), v,
                             preferred_element_type=jnp.float32)
        with jax.named_scope(INDEXER_SCOPE):
            target = probs.sum(axis=(0, 1)) / heads
    with jax.named_scope(INDEXER_SCOPE):
        target = lax.stop_gradient(target)
        log_index = jax.nn.log_softmax(
            jnp.where(keep, score, jnp.finfo(jnp.float32).min), axis=-1)
        seen = keep & (target > 0)
        kl = jnp.where(seen, target * (jnp.log(jnp.where(seen, target, 1.0))
                                       - log_index), 0.0).sum()
    if not kernel:
        out = out.reshape(rows, heads, head_dim)
    return out, lse, kl, keep.sum(dtype=jnp.int32)


def _one_sequence(q, k, v, index_q, index_k, index_w, *, topk, block, scale,
                  kernel):
    """The blocks of one sequence, in spans of ``topk`` keys: the queries
    of a span see the keys up to the span's end and no later one (62.5% of
    the [s, s] pairs at s = 4 topk; the first span selects nothing), and
    its blocks run one after the other under ``lax.map``. In the kernel
    form a block is handed all keys, one shape for every span, and the
    kernels stop at the tile of the block's last query."""
    s, heads = q.shape[:2]
    span = block * max(1, topk // block)
    saved = [_SAVED]
    if kernel:
        # imported here: a process that never reaches this line (any CPU
        # run, float32 operands) pays for no kernel library
        # (tests/test_import_footprint.py)
        from byteps_tpu.ops.sparse_flash import SAVED, renormalised

        saved.append(SAVED)
        q = q.reshape(s, -1)     # as the kernels read it and write ``out``
    run = jax.checkpoint(
        partial(_block, topk=topk, scale=scale, kernel=kernel),
        policy=jax.checkpoint_policies.save_only_these_names(*saved))
    outs, lses, kl, selected = [], [], 0.0, 0
    for lo in range(0, s, span):
        hi = min(lo + span, s)

        def blocks(a):
            return a[lo:hi].reshape((hi - lo) // block, block, *a.shape[1:])

        out, lse, kl_b, selected_b = lax.map(
            lambda xs: run(*xs, *((k, v) if kernel else (k[:hi], v[:hi])),
                           index_k[:hi]),
            (blocks(q), blocks(index_q), blocks(index_w),
             jnp.arange(lo, hi, block)))
        outs.append(out.reshape(hi - lo, *out.shape[2:]))
        if kernel:
            lses.append(lse.reshape(hi - lo, -1))
        kl, selected = kl + kl_b.sum(), selected + selected_b.sum()
    out = jnp.concatenate(outs)
    if kernel:
        # the normaliser's gradient, for all blocks at once: it reads the
        # output the caller's next layer keeps, so no block keeps its own
        out = renormalised(out, jnp.concatenate(lses)).reshape(s, heads, -1)
    return out, kl / s, selected


def sparse_attention(q, k, v, index_q, index_k, index_w, *, topk: int,
                     block: int = 512, scale=None):
    """Causal attention of every query over its ``topk`` highest-scoring
    earlier keys (itself included; all of them while there are no more
    than ``topk``).

    q [b, s, h, dh]; k, v [b, s, hk, dh] with hk dividing h, query head
    c * (h / hk) + i reading key-value head c; index_q [b, s, hi, di],
    index_k [b, s, di] (one key head) and index_w [b, s, hi] in float32.
    The index score of key s' for query t is sum_j index_w[t, j] *
    relu(index_q[t, j] . index_k[s']), in float32 at the highest matmul
    precision: which keys a query reaches must not turn on bf16 rounding.
    Attention logits and the softmax are float32; the probabilities meet
    ``v`` in ``v``'s dtype with float32 accumulation.

    Returns ``(out [b, s, h, dh] in q's dtype, index_loss, selected)``:
    ``index_loss`` = mean over queries of KL(p_t || softmax of the index
    scores over the selected keys), p_t the attention probabilities summed
    over heads and divided by h, detached; ``selected`` [b] int32 the
    number of (query, key) pairs attended, sum_t min(t + 1, topk) when the
    selection is what it says.
    """
    s, head_dim = q.shape[1], q.shape[-1]
    block = min(block, s)
    if s % block or q.shape[2] % k.shape[2]:
        raise ValueError(f"sequence {s} must be a multiple of the block "
                         f"{block}, heads {q.shape[2]} of the key-value "
                         f"heads {k.shape[2]}")
    from byteps_tpu.monitor import metrics

    metrics.inc_counter(ATTEND_SITES)
    kernel = attend_form(
        jax.default_backend(), jnp.result_type(q, k, v), head_dim,
        q.shape[2] // k.shape[2], block) == "kernel"
    if kernel:
        metrics.inc_counter(KERNEL_SITES)
    one = partial(_one_sequence, topk=topk, block=block, kernel=kernel,
                  scale=head_dim ** -0.5 if scale is None else scale)
    out, index_loss, selected = jax.vmap(one)(q, k, v, index_q, index_k,
                                              index_w)
    return out.astype(q.dtype), index_loss.mean(), selected


def publish_dsa_stats(dsa_stats) -> dict:
    """The ``"dsa_stats"`` collection of a model applied with it mutable
    (per layer ``selected`` and ``causal``, the (query, key) pairs attended
    and those a dense causal attention would attend) to
    ``monitor/metrics.py``: gauge ``bps_dsa_kept_keys_ratio`` (selected over
    causal, all layers together), counter ``bps_dsa_selected_keys_total``.
    Returns what it published."""
    import numpy as np

    from byteps_tpu.monitor import metrics

    selected = causal = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(dsa_stats)[0]:
        total = int(np.asarray(leaf, np.int64).sum())
        if "selected" in jax.tree_util.keystr(path):
            selected += total
        else:
            causal += total
    if not causal:
        return {}
    out = {"bps_dsa_kept_keys_ratio": selected / causal,
           "bps_dsa_selected_keys_total": float(selected)}
    metrics.set_gauge("bps_dsa_kept_keys_ratio",
                      out["bps_dsa_kept_keys_ratio"])
    metrics.inc_counter("bps_dsa_selected_keys_total",
                        out["bps_dsa_selected_keys_total"])
    return out
