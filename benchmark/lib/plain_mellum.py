"""Plain reference: the Mellum decoder (``JetBrains/Mellum2-12B-A2.5B-
Instruct``) in jax.numpy.

The forward pass of ``byteps_tpu.models.mellum`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no
kernel, no restricted grid, no repeated key head, no sorted permutation, no
grouped matmul, no pass over held rows. What a layer shares with Laguna's —
the rounding matmul, RMSNorm, the rotary embedding as a complex
multiplication, YaRN's frequencies in numpy, attention over all keys with
the band as a mask — is ``plain_laguna.py``'s and ``plain_kimi_linear.py``'s,
the benchmark's own. A layer is ``a = x + Attn(N1(x))``, ``y = a +
MoE(N2(a))``; a layer's kind is read off the ``layer_types`` it is told.

1. **Attention.** ``q = h W_q`` [s, key heads, group, head_dim] — query
   head i is member ``i % group`` of key head ``i // group`` — ``k, v = h
   W_k, h W_v`` [s, key heads, head_dim]. Rotary over the whole head
   (``plain_laguna.rotate``: entries ``(j, j + head_dim / 2)`` one complex
   number times ``factor exp(i pos w_j)``): a windowed layer ``w_j =
   theta^(-2j / head_dim)``, ``factor`` 1; a global layer YaRN's
   frequencies (``plain_laguna.yarn_frequencies``) and its attention
   factor, from the source's ``rope_parameters``. Scores ``q k / sqrt(head_dim)`` in
   float32 over all keys with the band as a mask — ``0 <= q_pos - k_pos``,
   and ``< window`` in a windowed layer — in blocks of ``query_block``
   queries, exact softmax (``plain_laguna.banded_attention``), then
   ``W_o``. No gate and no q/k norm.
2. **Expert layer** (``experts``). ``p = softmax(h W_r)`` over all E in
   float32 at the highest precision; the chosen set the first ``top_k`` of
   a stable ``argsort`` of ``-p``; weights ``p_j / sum over the chosen`` on
   the chosen and 0 elsewhere; every expert of the **share** ``(first_expert,
   held)`` — the tree holds exactly those — is applied to every token, one
   at a time, times the token's weight for it. What the experts held
   elsewhere would add is left out; no shared expert.
3. Final RMSNorm, the untied head, next-token NLL, in blocks of
   ``head_rows`` rows (recomputed).

Each half of a layer is recomputed in the backward pass. ``dtype`` is the
matmul operands' (float32 accumulation always): float32, the default and
what the cell's comparison runs, takes every matmul at the highest
precision; bfloat16 is the program's own rounding, there for the reading
one precision below. The residual stream, norms, rotation, logits, softmax
and router are float32 in both.

Returns the per-position negative log-likelihood [rows, s - 1] (the loss is
a weighted sum over positions, ``benchmark/lib/reference.py``).
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.plain_kimi_linear import F32, HIGHEST, _matmul, _rms_norm
from benchmark.lib.plain_laguna import (WINDOWED, banded_attention, rotate,
                                        yarn_frequencies)


def rotary_of(group, head_dim):
    """``(rotary, frequencies [head_dim / 2], factor)`` of one kind of layer
    from its group of the source's ``rope_parameters``: the whole head
    rotates (no key names a partial factor)."""
    if group["rope_type"] == "yarn":
        return head_dim, yarn_frequencies(
            head_dim, group["rope_theta"], group["factor"],
            group["original_max_position_embeddings"], group["beta_fast"],
            group["beta_slow"]), group["attention_factor"]
    own = group["rope_theta"] ** (-2.0 * np.arange(head_dim // 2) / head_dim)
    return head_dim, own.astype(np.float32), 1.0


def gate_weights(x, router, top_k, renormalise=True):
    """[T, E] float32: step 2's weight of every expert for every token, 0
    off the chosen set. ``renormalise`` False leaves the chosen
    probabilities as they are (a test's control)."""
    probs = jax.nn.softmax(jnp.dot(x.astype(F32), router, precision=HIGHEST),
                           axis=-1)
    chosen = jnp.argsort(-probs, axis=-1, stable=True)[:, :top_k]
    mask = (chosen[:, :, None]
            == jnp.arange(router.shape[1])[None, None, :]).any(axis=1)
    kept = jnp.where(mask, probs, 0.0)
    return kept / kept.sum(axis=-1, keepdims=True) if renormalise else kept


def experts(x, p, *, top_k, share, dtype, renormalise=True):
    """x [T, d]; ``share`` = (first_expert, held), the experts whose weights
    ``p`` holds. Their part of the layer's output, [T, d] in x's dtype."""
    first_expert, held = share
    if p["gate"].shape[0] != held:
        raise ValueError(f"the tree holds {p['gate'].shape[0]} experts, the "
                         f"share names {held}")
    weight = gate_weights(x, p["router"], top_k, renormalise)[
        :, first_expert:first_expert + held]

    @jax.checkpoint
    def one_expert(acc, inputs):
        w_gate, w_up, w_down, w_e = inputs
        hidden = (jax.nn.silu(_matmul(x, w_gate, dtype))
                  * _matmul(x, w_up, dtype))
        return acc + w_e[:, None] * _matmul(hidden, w_down, dtype).astype(
            F32), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, F32),
                        (p["gate"], p["up"], p["down"], weight.T))
    return y.astype(x.dtype)


def attention(x, p, *, head_dim, window, rotary, dtype, query_block,
              interleaved=False):
    """One sequence. x [s, d] (the normalised hidden state). ``interleaved``
    is the other head grouping (query head i reads key head ``i % key
    heads``: a test's control)."""
    s = x.shape[0]
    kv_heads = p["k"]["kernel"].shape[1] // head_dim
    q = _matmul(x, p["q"]["kernel"], dtype)
    q = (jnp.swapaxes(q.reshape(s, -1, kv_heads, head_dim), 1, 2)
         if interleaved else q.reshape(s, kv_heads, -1, head_dim))
    k, v = (_matmul(x, p[name]["kernel"], dtype).reshape(s, kv_heads,
                                                         head_dim)
            for name in "kv")
    out = banded_attention(rotate(q, *rotary), rotate(k, *rotary), v,
                           window=window, dtype=dtype,
                           query_block=query_block)
    if interleaved:
        out = jnp.swapaxes(out, 1, 2)
    return _matmul(out.reshape(s, -1), p["o"]["kernel"], dtype)


def causal_lm_nll(params, tokens, *, layer_types, head_dim, window,
                  rope_parameters, top_k, share, eps, dtype=F32,
                  query_block=256, head_rows=2048, renormalise=True,
                  interleaved=False):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), untied head.
    ``rope_parameters``: the source's group, one entry a kind of layer."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape
        rotaries = {kind: rotary_of(rope_parameters[kind], head_dim)
                    for kind in set(layer_types)}

        @partial(jax.checkpoint, static_argnums=(2,))
        def mixer_half(x, lp, kind):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            return x + jax.vmap(lambda row: attention(
                row, lp["attn"], head_dim=head_dim,
                window=window if kind == WINDOWED else None,
                rotary=rotaries[kind], dtype=dtype, query_block=query_block,
                interleaved=interleaved))(h)

        @jax.checkpoint
        def ffn_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            return x + experts(
                h.reshape(rows * s, d), lp["moe"], top_k=top_k, share=share,
                dtype=dtype, renormalise=renormalise).reshape(rows, s, d)

        for i in range(sum(name.startswith("layer_") for name in p)):
            lp = p[f"layer_{i}"]
            x = ffn_half(mixer_half(x, lp["mixer"], layer_types[i]),
                         lp["ffn"])
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        # a sequence's last row predicts nothing: it gets token 0 as its
        # target and is dropped, so that the rows divide into even blocks
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))

        @jax.checkpoint
        def head(inputs):
            h, target = inputs
            logp = jax.nn.log_softmax(
                _matmul(h, p["lm_head"]["kernel"], dtype).astype(F32))
            return -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]

        block = min(head_rows, s)
        nll = jax.lax.map(head, (x.reshape(-1, block, d),
                                 targets.reshape(-1, block)))
    return nll.reshape(rows, s)[:, :-1]
