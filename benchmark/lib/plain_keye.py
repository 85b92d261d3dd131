"""Plain reference: the Keye-VL-2.0 language model's decoder in jax.numpy.

The forward pass of ``byteps_tpu.models.keye`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no sorted
permutation, no grouped matmul, and the selection stated the plain way. Per
layer, in the configuration file's numbering:

1. ``h = RMSNorm(x)``; Q (``num_heads`` heads), K and V (``num_kv_heads``)
   of ``head_dim``, no biases; Q and K RMS-normalised per head over
   ``head_dim`` with one learned scale, then RoPE (half-split pairs) on
   positions 0..s-1.
2. The indexer on ``stop_gradient(h)`` in float32 at the highest matmul
   precision: ``qI`` [s, hi, di], ``kI = LayerNorm(h W_k)`` [s, di] (one key
   head), RoPE on both, ``w = h W_w / sqrt(hi di)``; index score
   ``I[t, s'] = sum_j w[t, j] relu(qI[t, j] . kI[s'])``.
3. The selection: for each query the indices ``lax.top_k`` returns for its
   causally masked scores — the ``index_topk`` highest, ties to the earlier
   key — scattered into a dense [queries, keys] mask and cut to s' <= t
   (for t + 1 < ``index_topk`` top_k also returns masked keys).
4. Attention over the selected keys: query head c * g + i reads key-value
   head c; softmax in float32 over all keys with the unselected ones at
   ``finfo.min``; the probabilities meet V in ``dtype`` with float32
   accumulation.
5. The indexer's loss: the probabilities summed over heads and divided by
   their number, detached, against the softmax of the index scores over the
   selected keys: the mean over queries of the KL divergence.
6. ``h = RMSNorm(x)``; the router in float32 at the highest precision,
   softmax over all experts, top-k, the chosen weights renormalised to sum
   to 1; every HELD expert (``first_expert ..`` as many as the tree has) is
   applied to every token, one at a time, and its output multiplied by the
   token's weight for it, zero if it is not among the token's top k. What
   the experts held elsewhere would add is left out. The load-balancing
   loss counts over all experts.
7. Final RMSNorm, the untied head, next-token NLL.

Steps 2-5 run in blocks of ``block`` queries over all keys (``lax.map``,
each block recomputed in the backward pass) so that the [heads, block, s]
float32 scores fit; the blocks tile the computation and do not change it.
The casts are the configuration's own (``dtype`` matmul operands with float32
accumulation; float32 residual stream, norms, rotary tables, softmaxes,
router and indexer), so reference and program differ by the order XLA sums
in, not by a precision. In float32 the matmuls run at the highest precision.

Returns the per-position negative log-likelihood (the loss is a weighted
sum over positions, ``benchmark/lib/reference.py``), the load-balancing loss
and the indexer's loss, each the mean over the layers; the latter two are
statistics of the rows handed in, which the configuration's
``reference_loss`` adds per shard.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MIN = jnp.finfo(jnp.float32).min


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _layer_norm(x, scale, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _matmul(x, w, dtype, spec="...d,dm->...m"):
    """``dtype`` operands, float32 accumulation, ``dtype`` result."""
    return jnp.einsum(spec, x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def _rope(x, theta):
    """[s, heads, head_dim], positions 0..s-1, half-split pairs."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = (x[..., :half].astype(jnp.float32),
              x[..., half:].astype(jnp.float32))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def selection(score, positions, topk):
    """[queries, keys] bool from index scores [queries, keys] of the
    queries at ``positions``: step 3."""
    n = score.shape[1]
    causal = jnp.arange(n)[None, :] <= positions[:, None]
    if n <= topk:
        return causal
    _, chosen = jax.lax.top_k(jnp.where(causal, score, -jnp.inf), topk)
    rows = jnp.arange(score.shape[0])[:, None]
    return jnp.zeros(score.shape, bool).at[rows, chosen].set(True) & causal


def _sparse_attention(x, p, *, num_heads, num_kv_heads, head_dim,
                      index_heads, index_topk, block, dtype, eps, theta):
    """One sequence. x [s, d] (the normalised hidden state). Returns
    (the attention layer's output [s, d] in ``dtype``, the indexer's
    loss)."""
    s = x.shape[0]
    q = _matmul(x, p["q"]["kernel"], dtype).reshape(s, num_heads, head_dim)
    k = _matmul(x, p["k"]["kernel"], dtype).reshape(s, num_kv_heads,
                                                    head_dim)
    v = _matmul(x, p["v"]["kernel"], dtype).reshape(s, num_kv_heads,
                                                    head_dim)
    q = _rope(_rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = _rope(_rms_norm(k, p["k_norm"]["scale"], eps), theta)

    pi = p["indexer"]
    xi = jax.lax.stop_gradient(x).astype(jnp.float32)
    index_dim = pi["k"]["kernel"].shape[1]
    qi = _rope(jnp.dot(xi, pi["q"]["kernel"], precision=HIGHEST).reshape(
        s, index_heads, index_dim), theta)
    ki = _rope(_layer_norm(jnp.dot(xi, pi["k"]["kernel"], precision=HIGHEST),
                           pi["k_norm"]["scale"], pi["k_norm"]["bias"],
                           eps)[:, None, :], theta)[:, 0]
    wi = (jnp.dot(xi, pi["w"]["kernel"], precision=HIGHEST)
          * (index_heads * index_dim) ** -0.5)

    @jax.checkpoint
    def one_block(inputs):
        q_b, qi_b, wi_b, positions = inputs
        score = jnp.einsum(
            "qjs,qj->qs", jax.nn.relu(jnp.einsum(
                "qjd,sd->qjs", qi_b, ki, precision=HIGHEST)), wi_b,
            precision=HIGHEST)                                   # [B, s]
        keep = selection(jax.lax.stop_gradient(score), positions,
                         index_topk)
        group = num_heads // num_kv_heads
        logits = jnp.einsum(
            "qcgd,scd->cgqs", q_b.reshape(-1, num_kv_heads, group, head_dim),
            k, preferred_element_type=jnp.float32) * head_dim ** -0.5
        probs = jax.nn.softmax(jnp.where(keep, logits, MIN), axis=-1)
        out = jnp.einsum("cgqs,scd->qcgd", probs.astype(dtype), v,
                         preferred_element_type=jnp.float32).astype(dtype)
        target = jax.lax.stop_gradient(probs.sum(axis=(0, 1)) / num_heads)
        log_index = jax.nn.log_softmax(jnp.where(keep, score, MIN), axis=-1)
        seen = keep & (target > 0)
        kl = jnp.where(seen, target * (
            jnp.log(jnp.where(seen, target, 1.0)) - log_index), 0.0).sum()
        return out.reshape(-1, num_heads * head_dim), kl

    block = min(block, s)
    blocks = lambda a: a.reshape(s // block, block, *a.shape[1:])  # noqa: E731
    out, kl = jax.lax.map(one_block, (blocks(q), blocks(qi), blocks(wi),
                                      blocks(jnp.arange(s))))
    return (_matmul(out.reshape(s, -1), p["o"]["kernel"], dtype),
            kl.sum() / s)


def _experts(x, p, top_k, first_expert, dtype):
    """x: [T, d]. Returns (the held experts' part of the layer's output
    [T, d] in x's dtype, load_balance)."""
    t, e = x.shape[0], p["router"].shape[1]
    held = p["gate"].shape[0]
    logits = jnp.dot(x.astype(jnp.float32), p["router"], precision=HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                     # [T, E]
    _, chosen = jax.lax.top_k(probs, top_k)                     # [T, k]
    mask = (chosen[:, :, None] == jnp.arange(e)[None, None, :]).any(axis=1)
    weight = jnp.where(mask, probs, 0.0)
    weight = weight / weight.sum(axis=-1, keepdims=True)   # over the chosen
    weight = weight[:, first_expert:first_expert + held]

    @jax.checkpoint
    def one_expert(acc, inputs):
        w_gate, w_up, w_down, w_e = inputs
        hidden = (jax.nn.silu(_matmul(x, w_gate, dtype))
                  * _matmul(x, w_up, dtype))
        out = _matmul(hidden, w_down, dtype)
        return acc + w_e[:, None] * out.astype(jnp.float32), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, jnp.float32),
                        (p["gate"], p["up"], p["down"], weight.T))
    counts = mask.sum(axis=0).astype(jnp.float32)
    load_balance = (counts * probs.mean(axis=0)).sum() * (e / (t * top_k))
    return y.astype(x.dtype), load_balance


def causal_lm_nll_and_aux(params, tokens, *, num_layers, num_heads,
                          num_kv_heads, head_dim, top_k, first_expert,
                          index_heads, index_topk, block, eps, rope_theta,
                          dtype):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), untied head, and
    the load-balancing loss (over ``tokens``' rows x s tokens) and the
    indexer's loss (mean over rows), each the mean over the layers."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape
        load_balance = index_loss = 0.0
        for i in range(num_layers):
            lp = p[f"layer_{i}"]
            h = _rms_norm(x, lp["attn_norm"]["scale"], eps)
            y, kl = jax.vmap(lambda h_row: _sparse_attention(
                h_row, lp["attn"], num_heads=num_heads,
                num_kv_heads=num_kv_heads, head_dim=head_dim,
                index_heads=index_heads, index_topk=index_topk, block=block,
                dtype=dtype, eps=eps, theta=rope_theta))(h)
            x = x + y
            y, lb = _experts(
                _rms_norm(x, lp["moe_norm"]["scale"], eps).reshape(
                    rows * s, d), lp["moe"], top_k, first_expert, dtype)
            x = x + y.reshape(rows, s, d)
            load_balance, index_loss = (load_balance + lb / num_layers,
                                        index_loss + kl.mean() / num_layers)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logits = _matmul(x, p["lm_head"]["kernel"], dtype)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32),
                                  axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll, load_balance, index_loss
