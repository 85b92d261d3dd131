"""Plain reference: the JoyAI-LLM-Flash decoder (DeepSeek-V3's layers, arXiv
2412.19437 sections 2.1 and 2.2) in jax.numpy.

The forward pass of ``byteps_tpu.models.joyai`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no
kernel, no sorted permutation, no grouped matmul, no padded stream. What a
DeepSeek-V3-shaped layer shares with Kimi-Linear's — RMSNorm, the rounding
matmul, SwiGLU and the held experts behind the literal ``argsort`` gate
(``gate_weights``) — is ``plain_kimi_linear.py``'s, the benchmark's own. A
layer is ``a = x + MLA(N1(x))``, ``y = a + FFN(N2(a))``; a layer's
feed-forward is read off its parameters (``ffn/mlp``: the dense SwiGLU;
``ffn/moe``: the experts).

1. **MLA with a low-rank query and a rotary key part.** ``c_q = RMSNorm(h
   W_qa)``, ``q = c_q W_qb`` [heads, nope + rope]; ``c = h W_kva``; ``[k_nope
   | v] = RMSNorm(c[:kv_rank]) W_kvb`` per head; ``k_pe = c[kv_rank:]``, one
   for all heads. The rotary embedding on ``q``'s last ``rope`` entries and
   on ``k_pe`` as a **complex multiplication**: entries ``(2j, 2j + 1)``
   are the real and imaginary part of one number, multiplied by ``exp(i pos
   theta^(-2j / rope))``, in ``rope_dtype`` (float32 is the
   configuration's; bfloat16 is there for the test that the comparison can
   tell). ``k = [k_nope | k_pe]``; causal softmax of ``q k^T (nope +
   rope)^-1/2`` in float32 over all keys, in blocks of ``query_block``
   queries (``lax.map``, each recomputed in the backward pass; queries
   beyond the last are zeros that are cut off again), the probabilities
   meeting V in ``dtype``; ``W_o``.
2. **Expert layer**: ``plain_kimi_linear.experts``.
3. Final RMSNorm, the untied head, next-token NLL at rows 0..s-2, in blocks
   of ``head_rows`` rows (recomputed; rows beyond the last are zeros that
   are cut off again).
4. **MTP, depth 1**, by explicit shifts of ``tokens``, over rows i = 0..s-3
   and no others: ``z_i = [RMSNorm_e(Embed(tokens[i + 1])) ;
   RMSNorm_h(h_i)] W_eh`` with ``h`` the last main layer's output before
   the final norm and ``Embed`` the main table; one more whole layer over
   those s - 2 rows, row i at position i; ``Head(RMSNorm_s(.))`` with the
   main head; row i's target is ``tokens[i + 2]``. ``mtp_leaves`` names
   another tree for the module to read ``embed`` and ``lm_head`` from: the
   main tree is the configuration's (they are its leaves); another is there
   for the test that the comparison can tell a detached or second leaf.

Each half of a layer is recomputed in the backward pass. The casts are the
configuration's own (``dtype`` matmul operands with float32 accumulation;
float32 residual stream, norms, rotation, softmax, router), so reference
and program differ by the order sums are taken in, never by a precision. In
float32 the matmuls run at the highest precision.

Returns both per-position negative log-likelihoods, ``(main [rows, s - 1],
mtp [rows, s - 2])`` (the loss is a weighted sum over positions,
``benchmark/lib/reference.py``).
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from benchmark.lib.plain_kimi_linear import (F32, MIN, _matmul, _rms_norm,
                                             _swiglu, experts)


def rotate(x, theta, rope_dtype=F32):
    """x [s, heads, rope]: step 1's rotation of row p by position p."""
    s, heads, rope = x.shape
    pairs = x.astype(rope_dtype).reshape(s, heads, rope // 2, 2)
    rate = theta ** (-jnp.arange(0, rope, 2, dtype=F32) / rope)
    angle = (jnp.arange(s, dtype=F32)[:, None] * rate).astype(rope_dtype)
    if rope_dtype == F32:
        turned = (jax.lax.complex(pairs[..., 0], pairs[..., 1])
                  * jnp.exp(1j * angle)[:, None, :])
        re, im = turned.real, turned.imag
    else:            # no complex number is that narrow: the same product
        cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
        re = pairs[..., 0] * cos - pairs[..., 1] * sin
        im = pairs[..., 0] * sin + pairs[..., 1] * cos
    return jnp.stack([re, im], axis=-1).reshape(s, heads, rope).astype(
        x.dtype)


def causal_attention(q, k, v, *, dtype, query_block):
    """q, k [s, heads, d_k], v [s, heads, d_v] -> [s, heads, d_v]: every
    query over every key at or before it, a block of queries at a time."""
    s, heads, _ = q.shape
    block = min(query_block, s)
    padded = -(-s // block) * block

    @jax.checkpoint
    def one_block(inputs):
        q_b, positions = inputs
        logits = jnp.einsum("qhd,shd->hqs", q_b, k,
                            preferred_element_type=F32) * q.shape[-1] ** -0.5
        causal = jnp.arange(s)[None, :] <= positions[:, None]
        probs = jax.nn.softmax(jnp.where(causal, logits, MIN), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs.astype(dtype), v,
                          preferred_element_type=F32).astype(dtype)

    out = jax.lax.map(one_block, (
        jnp.pad(q, ((0, padded - s), (0, 0), (0, 0))).reshape(
            padded // block, block, heads, -1),
        jnp.arange(padded).reshape(-1, block)))
    return out.reshape(padded, heads, -1)[:s]


def _mla(x, p, *, heads, kv_rank, v_dim, rope_dim, theta, dtype, eps,
         query_block, rope_dtype):
    """One sequence. x [s, d] (the normalised hidden state)."""
    s = x.shape[0]
    q = _matmul(_rms_norm(_matmul(x, p["q_a"]["kernel"], dtype),
                          p["q_norm"]["scale"], eps),
                p["q_b"]["kernel"], dtype).reshape(s, heads, -1)
    c = _matmul(x, p["kv_a"]["kernel"], dtype)
    kv = _matmul(_rms_norm(c[:, :kv_rank], p["kv_norm"]["scale"], eps),
                 p["kv_b"]["kernel"], dtype).reshape(s, heads, -1)
    k_pe = rotate(c[:, None, kv_rank:], theta, rope_dtype)
    q = jnp.concatenate([q[..., :-rope_dim],
                         rotate(q[..., -rope_dim:], theta, rope_dtype)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :-v_dim],
                         jnp.broadcast_to(k_pe, (s, heads, rope_dim))],
                        axis=-1)
    out = causal_attention(q, k, kv[..., -v_dim:], dtype=dtype,
                           query_block=query_block)
    return _matmul(out.reshape(s, -1), p["o"]["kernel"], dtype)


def causal_lm_nll(params, tokens, *, heads, kv_rank, v_dim, rope_dim, theta,
                  top_k, first_expert, routed_scale, eps, dtype,
                  query_block=512, head_rows=2048, rope_dtype=F32,
                  mtp_leaves=None):
    """``(main, mtp)``: the NLL of token i + 1 at rows 0..s-2 and of token i
    + 2 at rows 0..s-3, untied head."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        table = p["embed"]["embedding"]
        rows, s = tokens.shape
        d = table.shape[1]

        @jax.checkpoint
        def mixer_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            return x + jax.vmap(lambda row: _mla(
                row, lp["mla"], heads=heads, kv_rank=kv_rank, v_dim=v_dim,
                rope_dim=rope_dim, theta=theta, dtype=dtype, eps=eps,
                query_block=query_block, rope_dtype=rope_dtype))(h)

        @jax.checkpoint
        def ffn_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            if "mlp" in lp:
                return x + _swiglu(h, lp["mlp"], dtype)
            return x + experts(
                h.reshape(-1, d), lp["moe"], top_k=top_k,
                first_expert=first_expert, routed_scale=routed_scale,
                dtype=dtype).reshape(x.shape)

        def layer(x, lp):
            return ffn_half(mixer_half(x, lp["mixer"]), lp["ffn"])

        def head(h, targets, leaves):
            """h [rows, n, d] (normalised), targets [rows, n] -> [rows, n]."""

            @jax.checkpoint
            def head_block(inputs):
                h, target = inputs
                logp = jax.nn.log_softmax(_matmul(
                    h, leaves["lm_head"]["kernel"], dtype).astype(F32))
                return -jnp.take_along_axis(logp, target[:, None],
                                            axis=-1)[:, 0]

            n = targets.size
            block = min(head_rows, n)
            pad = -(-n // block) * block - n
            nll = jax.lax.map(head_block, (
                jnp.pad(h.reshape(n, d), ((0, pad), (0, 0))).reshape(
                    -1, block, d),
                jnp.pad(targets.reshape(n), (0, pad)).reshape(-1, block)))
            return nll.reshape(-1)[:n].reshape(targets.shape)

        x = table[tokens]                          # float32 residual stream
        for i in range(sum(name.startswith("layer_") for name in p)):
            x = layer(x, p[f"layer_{i}"])
        main = head(_rms_norm(x, p["final_norm"]["scale"], eps)[:, :-1],
                    tokens[:, 1:], p)

        m = p["mtp"]
        leaves = p if mtp_leaves is None else mtp_leaves["params"]
        z = _matmul(jnp.concatenate(
            [_rms_norm(leaves["embed"]["embedding"][tokens[:, 1:-1]],
                       m["embed_norm"]["scale"], eps),
             _rms_norm(x[:, :-2], m["hidden_norm"]["scale"], eps)], axis=-1),
            m["eh_proj"]["kernel"], dtype).astype(F32)
        mtp = head(_rms_norm(layer(z, m["block"]), m["norm"]["scale"], eps),
                   tokens[:, 2:], leaves)
    return main, mtp
