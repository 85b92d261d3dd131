"""Plain reference: the Kimi-Linear decoder in jax.numpy.

The forward pass of ``byteps_tpu.models.kimi_linear`` written out over the
same parameter tree, with nothing of the program in it: no flax module, no
chunked scan, no kernel, no sorted permutation, no grouped matmul. A layer is
``a = x + Mix(N1(x))``, ``y = a + FFN(N2(a))``, RMSNorm eps ``eps``; which
mixer a layer has is read off its parameters (``mixer/kda`` or ``mixer/mla``),
and so is its feed-forward (``ffn/mlp``: the leading dense SwiGLU; ``ffn/moe``:
the experts).

1. **KDA**, ``heads`` heads of ``head_dim`` keys and values. ``q``, ``k``,
   ``v`` = SiLU of a causal depthwise convolution (``lax.conv_general_
   dilated``, one group a channel, zeros before the sequence) of the three
   projections; ``q`` and ``k`` divided by their norm over a head
   (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times ``head_dim^-1/2``. Log-decay
   ``g = -exp(A_log[head]) softplus((h W_fa) W_fb + dt_bias)`` per channel,
   its two projections in float32 at the highest precision; ``beta =
   sigmoid(h W_beta)`` per head. Then **token by token** (``lax.scan`` over
   the sequence, float32, products and sums and no matmul), per head with
   ``S`` [keys, values] from zero:

       S <- exp(g_t) . S (rows);  u = beta_t (v_t - S^T k_t);
       S <- S + k_t u^T;          o_t = S^T q_t.

   The scan runs in blocks of ``scan_block`` tokens, each recomputed in the
   backward pass, which then keeps one state a block and not one a token
   (16,384 states of 2 MB would be 34 GB a layer). ``state_dtype`` rounds the
   state after every token: float32 is the configuration's; bfloat16 is
   there for the test that the comparison can tell.
   Output ``(RMSNorm_head(o) * sigmoid((h W_ga) W_gb + b_g)) W_o``.
2. **MLA without position embedding.** ``q = h W_q`` [heads, nope + rope];
   ``c = h W_kva``; ``[k_nope | v] = RMSNorm(c[:kv_rank]) W_kvb`` per head,
   ``k = [k_nope | c[kv_rank:]]`` (the last part one for all heads, no
   rotary embedding on it or on q's); causal softmax of ``q k^T (nope +
   rope)^-1/2`` in float32 over all keys, in blocks of ``query_block``
   queries (``lax.map``, each recomputed in the backward pass), the
   probabilities meeting V in ``dtype``; ``W_o``.
3. **Expert layer.** ``s = sigmoid(h W_r)`` in float32 at the highest
   precision; the chosen set the first ``top_k`` of a stable ``argsort`` of
   ``-(s + select_bias)``; weights ``s_j / (sum over the chosen + 1e-20) x
   routed_scale`` on the chosen and 0 elsewhere; every HELD expert
   (``first_expert ..`` as many as the tree has) is applied to every token,
   one at a time, times the token's weight for it. What the experts held
   elsewhere would add is left out. Plus the shared expert, on every token.
4. Final RMSNorm, the untied head, next-token NLL, in blocks of
   ``head_rows`` rows (recomputed), so that one block's logits live.

Each half of a layer is recomputed in the backward pass. The casts are the
configuration's own (``dtype`` matmul operands with float32 accumulation;
float32 residual stream, norms, gates, decay, state, softmax, router), so
reference and program differ by the order sums are taken in and by the
algorithm of step 1 — token by token here, chunked there — never by a
precision. In float32 the matmuls run at the highest precision.

Returns the per-position negative log-likelihood (the loss is a weighted sum
over positions, ``benchmark/lib/reference.py``).
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
MIN = jnp.finfo(jnp.float32).min
F32 = jnp.float32


def _rms_norm(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _matmul(x, w, dtype):
    """``dtype`` operands, float32 accumulation, ``dtype`` result."""
    return jnp.einsum("...d,dm->...m", x.astype(dtype), w.astype(dtype),
                      preferred_element_type=F32).astype(dtype)


def _swiglu(x, p, dtype):
    return _matmul(jax.nn.silu(_matmul(x, p["gate"]["kernel"], dtype))
                   * _matmul(x, p["up"]["kernel"], dtype),
                   p["down"]["kernel"], dtype)


def _conv_silu(y, w):
    """y [s, channels] float32, w [taps, channels]: SiLU of the causal
    depthwise convolution."""
    taps, channels = w.shape
    out = jax.lax.conv_general_dilated(
        y.T[None], w.T[:, None, :], window_strides=(1,),
        padding=[(taps - 1, 0)], feature_group_count=channels,
        precision=HIGHEST)                      # [1, channels, s]
    return jax.nn.silu(out[0].T)


def delta_rule(q, k, v, g, beta, *, scan_block, state_dtype=F32):
    """Step 1's recurrence for one sequence: q, k, g [s, heads, d_k], v [s,
    heads, d_v], beta [s, heads], float32 -> o [s, heads, d_v]."""
    s, heads, d_k = q.shape

    def token(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[:, :, None] * state
        u = beta_t[:, None] * (v_t - (state * k_t[:, :, None]).sum(axis=1))
        state = (state + k_t[:, :, None] * u[:, None, :]).astype(
            state_dtype).astype(F32)
        return state, (state * q_t[:, :, None]).sum(axis=1)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    scan_block = min(scan_block, s)
    state = jnp.zeros((heads, d_k, v.shape[-1]), F32)
    inputs = tuple(x.reshape(s // scan_block, scan_block, *x.shape[1:])
                   for x in (q, k, v, g, beta))
    return jax.lax.scan(block, state, inputs)[1].reshape(s, heads, -1)


def _kda(x, p, *, heads, dtype, eps, scan_block, state_dtype):
    """One sequence. x [s, d] (the normalised hidden state). Three stages,
    each recomputed in the backward pass (what is kept between them is the
    projections' output, q, k, v, g, beta and o, not the two dozen float32
    [s, 4096] tensors on the way)."""
    s = x.shape[0]

    @jax.checkpoint
    def prepared(projected, convs, decay_in, beta_in, a_log, dt_bias):
        def unit(y):
            return y / jnp.sqrt((y * y).sum(axis=-1, keepdims=True) + 1e-6)

        q, k, v = (_conv_silu(y.astype(F32), w).reshape(s, heads, -1)
                   for y, w in zip(projected, convs))
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            decay_in + dt_bias).reshape(s, heads, -1)
        return (unit(q) * q.shape[-1] ** -0.5, unit(k), v, g,
                jax.nn.sigmoid(beta_in.astype(F32)))

    @jax.checkpoint
    def gated(o, gate_in, scale):
        return (_rms_norm(o, scale, eps).reshape(s, -1)
                * jax.nn.sigmoid(gate_in.astype(F32)))

    decay_in = jnp.dot(jnp.dot(x.astype(F32), p["f_a"]["kernel"],
                               precision=HIGHEST),
                       p["f_b"]["kernel"], precision=HIGHEST)
    o = jax.checkpoint(partial(delta_rule, scan_block=scan_block,
                               state_dtype=state_dtype))(*prepared(
        [_matmul(x, p[name]["kernel"], dtype) for name in "qkv"],
        [p[name + "_conv"] for name in "qkv"], decay_in,
        _matmul(x, p["beta"]["kernel"], dtype), p["A_log"], p["dt_bias"]))
    gate_in = (_matmul(_matmul(x, p["g_a"]["kernel"], dtype),
                       p["g_b"]["kernel"], dtype)
               + p["g_b"]["bias"].astype(dtype))
    return _matmul(gated(o, gate_in, p["o_norm"]["scale"]),
                   p["o"]["kernel"], dtype)


def _mla(x, p, *, heads, kv_rank, v_dim, dtype, eps, query_block):
    """One sequence. x [s, d]."""
    s = x.shape[0]
    q = _matmul(x, p["q"]["kernel"], dtype).reshape(s, heads, -1)
    c = _matmul(x, p["kv_a"]["kernel"], dtype)
    kv = _matmul(_rms_norm(c[:, :kv_rank], p["kv_norm"]["scale"], eps),
                 p["kv_b"]["kernel"], dtype).reshape(s, heads, -1)
    v = kv[..., -v_dim:]
    k = jnp.concatenate(
        [kv[..., :-v_dim],
         jnp.broadcast_to(c[:, None, kv_rank:],
                          (s, heads, c.shape[1] - kv_rank))], axis=-1)

    @jax.checkpoint
    def one_block(inputs):
        q_b, positions = inputs
        logits = jnp.einsum("qhd,shd->hqs", q_b, k,
                            preferred_element_type=F32) * q.shape[-1] ** -0.5
        causal = jnp.arange(s)[None, :] <= positions[:, None]
        probs = jax.nn.softmax(jnp.where(causal, logits, MIN), axis=-1)
        return jnp.einsum("hqs,shd->qhd", probs.astype(dtype), v,
                          preferred_element_type=F32).astype(dtype)

    block = min(query_block, s)
    out = jax.lax.map(one_block, (q.reshape(s // block, block, heads, -1),
                                  jnp.arange(s).reshape(-1, block)))
    return _matmul(out.reshape(s, -1), p["o"]["kernel"], dtype)


def gate_weights(x, router, select_bias, top_k, routed_scale):
    """[T, E] float32: step 3's weight of every expert for every token, 0
    off the chosen set."""
    scores = jax.nn.sigmoid(jnp.dot(x.astype(F32), router,
                                    precision=HIGHEST))
    chosen = jnp.argsort(-(scores + select_bias), axis=-1,
                         stable=True)[:, :top_k]
    mask = (chosen[:, :, None]
            == jnp.arange(router.shape[1])[None, None, :]).any(axis=1)
    kept = jnp.where(mask, scores, 0.0)
    return kept / (kept.sum(axis=-1, keepdims=True) + 1e-20) * routed_scale


def experts(x, p, *, top_k, first_expert, routed_scale, dtype):
    """x [T, d]. The held experts' part of the layer's output plus the
    shared expert's, [T, d] in x's dtype."""
    held = p["gate"].shape[0]
    weight = gate_weights(x, p["router"], p["select_bias"], top_k,
                          routed_scale)[:, first_expert:first_expert + held]

    @jax.checkpoint
    def one_expert(acc, inputs):
        w_gate, w_up, w_down, w_e = inputs
        hidden = (jax.nn.silu(_matmul(x, w_gate, dtype))
                  * _matmul(x, w_up, dtype))
        return acc + w_e[:, None] * _matmul(hidden, w_down, dtype).astype(
            F32), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, F32),
                        (p["gate"], p["up"], p["down"], weight.T))
    return y.astype(x.dtype) + _swiglu(x, p["shared"], dtype)


def causal_lm_nll(params, tokens, *, heads, kv_rank, v_dim, top_k,
                  first_expert, routed_scale, eps, dtype, scan_block=128,
                  query_block=512, head_rows=2048, state_dtype=F32):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), untied head."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape

        @jax.checkpoint
        def mixer_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            if "kda" in lp:
                mix = jax.vmap(lambda row: _kda(
                    row, lp["kda"], heads=heads, dtype=dtype, eps=eps,
                    scan_block=scan_block, state_dtype=state_dtype))
            else:
                mix = jax.vmap(lambda row: _mla(
                    row, lp["mla"], heads=heads, kv_rank=kv_rank,
                    v_dim=v_dim, dtype=dtype, eps=eps,
                    query_block=query_block))
            return x + mix(h)

        @jax.checkpoint
        def ffn_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            if "mlp" in lp:
                return x + _swiglu(h, lp["mlp"], dtype)
            return x + experts(
                h.reshape(rows * s, d), lp["moe"], top_k=top_k,
                first_expert=first_expert, routed_scale=routed_scale,
                dtype=dtype).reshape(rows, s, d)

        for i in range(sum(name.startswith("layer_") for name in p)):
            lp = p[f"layer_{i}"]
            x = ffn_half(mixer_half(x, lp["mixer"]), lp["ffn"])
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
