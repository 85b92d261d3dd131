"""Plain reference: the Laguna decoder (``poolside/Laguna-XS.2``) in
jax.numpy.

The forward pass of ``byteps_tpu.models.laguna`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no
kernel, no restricted grid, no repeated key head, no sorted permutation, no
grouped matmul. What a layer shares with Kimi-Linear's — RMSNorm, the
rounding matmul, SwiGLU and the held experts behind the literal ``argsort``
gate — is ``plain_kimi_linear.py``'s, the benchmark's own. A layer is ``a =
x + Attn(N1(x))``, ``y = a + FFN(N2(a))``; a layer's query heads are read
off its parameters (``q``'s width over ``head_dim``), its kind off the
``layer_types`` it is told, its feed-forward off its parameters
(``ffn/mlp``: the dense SwiGLU; ``ffn/moe``: the experts).

1. **Attention.** ``q = h W_q`` [s, key heads, group, head_dim] — query
   head i is member ``i % group`` of key head ``i // group`` — ``k, v = h
   W_k, h W_v`` [s, key heads, head_dim]. The rotary embedding as a
   **complex multiplication**: of the first ``rotary`` entries of a head,
   ``(j, j + rotary / 2)`` are the real and imaginary part of one number,
   multiplied by ``factor exp(i pos w_j)``; the entries after them pass. A
   windowed layer: ``rotary`` the whole head, ``w_j = theta^(-2j /
   rotary)``, ``factor`` 1. A global layer: the configuration's partial
   factor of the head, YaRN's frequencies (``yarn_frequencies``, the
   equations of arXiv 2309.00071 in numpy) and its attention factor. Scores
   ``q k / sqrt(head_dim)`` in ``logits_dtype`` (float32 is the
   configuration's; bfloat16 is there for the test that a float32
   comparison can tell and for ``tools/attention_check.py``'s reading one
   precision below), over all keys with the band as a mask — ``0 <= q_pos - k_pos``,
   and ``< window`` in a windowed layer — in blocks of ``query_block``
   queries (``lax.map``, each recomputed in the backward pass; queries
   beyond the last are zeros that are cut off again), exact softmax, the
   probabilities meeting V in ``dtype``. Gate ``g = sigmoid(h W_g)`` [s,
   heads], float32: head i's output times ``g_i``; ``W_o``.
2. **Expert layer**: ``plain_kimi_linear.experts`` with a selection bias of
   zero (the scores alone choose).
3. Final RMSNorm, the untied head, next-token NLL, in blocks of
   ``head_rows`` rows (recomputed).

Each half of a layer is recomputed in the backward pass. The casts are the
configuration's own (``dtype`` matmul operands with float32 accumulation;
float32 residual stream, norms, rotation, logits, softmax, gate, router), so
reference and program differ by the order sums are taken in, never by a
precision. In float32 the matmuls run at the highest precision.

Returns the per-position negative log-likelihood [rows, s - 1] (the loss is
a weighted sum over positions, ``benchmark/lib/reference.py``).
"""

from __future__ import annotations

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.plain_kimi_linear import (F32, _matmul, _rms_norm,
                                             _swiglu, experts)

WINDOWED = "sliding_attention"


def yarn_frequencies(rotary, theta, factor, original_max, beta_fast,
                     beta_slow):
    """[rotary / 2] float32: pair j's frequency under YaRN. ``f_j =
    theta^(-2j / rotary)``; the pair that turns ``beta`` times over
    ``original_max`` positions is ``r(beta) = rotary ln(original_max / (2 pi
    beta)) / (2 ln theta)``; between ``lo = floor(r(beta_fast))`` and ``hi =
    ceil(r(beta_slow))`` (clamped to 0 .. rotary - 1) the frequency goes
    linearly from ``f_j`` to ``f_j / factor``."""
    def turns(beta):
        return (rotary * math.log(original_max / (2 * math.pi * beta))
                / (2 * math.log(theta)))

    lo = max(math.floor(turns(beta_fast)), 0)
    hi = min(math.ceil(turns(beta_slow)), rotary - 1)
    j = np.arange(rotary // 2, dtype=np.float64)
    own = theta ** (-2.0 * j / rotary)
    ramp = np.clip((j - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    return ((1.0 - ramp) * own + ramp * own / factor).astype(np.float32)


def rotary_of(kind, head_dim, rope_parameters):
    """``(rotary, frequencies [rotary / 2], factor)`` of one kind of layer
    from the source's ``rope_parameters`` group."""
    p = rope_parameters[kind]
    rotary = int(head_dim * p["partial_rotary_factor"])
    if p["rope_type"] == "yarn":
        return rotary, yarn_frequencies(
            rotary, p["rope_theta"], p["factor"],
            p["original_max_position_embeddings"], p["beta_fast"],
            p["beta_slow"]), p["attention_factor"]
    own = p["rope_theta"] ** (-2.0 * np.arange(rotary // 2) / rotary)
    return rotary, own.astype(np.float32), 1.0


def rotate(x, rotary, frequencies, factor):
    """x [s, ..., head_dim]: step 1's rotation of row p by position p."""
    s, half = x.shape[0], rotary // 2
    lead = (s,) + (1,) * (x.ndim - 2) + (half,)
    turn = factor * jnp.exp(1j * (
        jnp.arange(s, dtype=F32)[:, None] * frequencies).reshape(lead))
    turned = jax.lax.complex(x[..., :half].astype(F32),
                             x[..., half:rotary].astype(F32)) * turn
    return jnp.concatenate(
        [turned.real.astype(x.dtype), turned.imag.astype(x.dtype),
         x[..., rotary:]], axis=-1)


def banded_attention(q, k, v, *, window, dtype, query_block,
                     logits_dtype=F32):
    """q [s, key heads, group, d], k, v [s, key heads, d] -> [s, key heads,
    group, d]: every query over the keys at or before it, the last
    ``window`` of them where a window is given, a block of queries at a
    time."""
    s = q.shape[0]
    block = min(query_block, s)
    padded = -(-s // block) * block

    @jax.checkpoint
    def one_block(inputs):
        q_b, positions = inputs
        logits = (jnp.einsum("qkgd,skd->kgqs", q_b, k,
                             preferred_element_type=F32)
                  * q.shape[-1] ** -0.5).astype(logits_dtype)
        back = positions[:, None] - jnp.arange(s)[None, :]
        seen = back >= 0
        if window is not None:
            seen &= back < window
        probs = jax.nn.softmax(
            jnp.where(seen, logits, jnp.finfo(logits_dtype).min), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", probs.astype(dtype), v,
                          preferred_element_type=F32).astype(dtype)

    out = jax.lax.map(one_block, (
        jnp.pad(q, ((0, padded - s),) + ((0, 0),) * 3).reshape(
            padded // block, block, *q.shape[1:]),
        jnp.arange(padded).reshape(-1, block)))
    return out.reshape(padded, *q.shape[1:])[:s]


def _attention(x, p, *, head_dim, window, rotary, dtype, query_block,
               logits_dtype):
    """One sequence. x [s, d] (the normalised hidden state)."""
    s = x.shape[0]
    kv_heads = p["k"]["kernel"].shape[1] // head_dim
    q = _matmul(x, p["q"]["kernel"], dtype).reshape(s, kv_heads, -1,
                                                    head_dim)
    k, v = (_matmul(x, p[name]["kernel"], dtype).reshape(s, kv_heads,
                                                         head_dim)
            for name in "kv")
    gate = jax.nn.sigmoid(_matmul(x, p["gate"]["kernel"], dtype).astype(F32))
    out = banded_attention(rotate(q, *rotary), rotate(k, *rotary), v,
                           window=window, dtype=dtype,
                           query_block=query_block,
                           logits_dtype=logits_dtype)
    gated = out.reshape(s, -1, head_dim).astype(F32) * gate[:, :, None]
    return _matmul(gated.reshape(s, -1), p["o"]["kernel"], dtype)


def causal_lm_nll(params, tokens, *, layer_types, head_dim, window,
                  rope_parameters, top_k, first_expert, routed_scale, eps,
                  dtype, query_block=256, head_rows=2048, logits_dtype=F32):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), untied head."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape
        rotaries = {kind: rotary_of(kind, head_dim, rope_parameters)
                    for kind in set(layer_types)}

        @partial(jax.checkpoint, static_argnums=(2,))
        def mixer_half(x, lp, kind):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            return x + jax.vmap(lambda row: _attention(
                row, lp["attn"], head_dim=head_dim,
                window=window if kind == WINDOWED else None,
                rotary=rotaries[kind], dtype=dtype,
                query_block=query_block, logits_dtype=logits_dtype))(h)

        @jax.checkpoint
        def ffn_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            if "mlp" in lp:
                return x + _swiglu(h, lp["mlp"], dtype)
            moe = dict(lp["moe"], select_bias=jnp.zeros(
                lp["moe"]["router"].shape[1], F32))
            return x + experts(
                h.reshape(rows * s, d), moe, top_k=top_k,
                first_expert=first_expert, routed_scale=routed_scale,
                dtype=dtype).reshape(rows, s, d)

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
