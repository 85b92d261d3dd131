"""Plain reference: the Qwen3-Next decoder in jax.numpy.

The forward pass of ``byteps_tpu.models.qwen3_next`` written out over the
same parameter tree, with nothing of the program in it: no flax module, no
chunked scan, no kernel, no sorted permutation, no grouped matmul. A layer is
``a = x + Mix(N1(x))``, ``y = a + MoE(N2(a))``; every ``N`` the zero-centred
RMSNorm ``x rsqrt(mean x^2 + eps) (1 + w)``; which mixer a layer has is read
off its parameters (``mixer/gdn`` or ``mixer/attn``).

1. **Gated DeltaNet**, ``h`` value heads over ``h_k`` key heads (read off
   ``A_log`` and the widths). ``[q; k; v; z] = h W_qkvz`` (``dtype``
   operands), ``[b; a] = h W_ba`` in float32 at the highest precision.
   ``(q, k, v)`` = SiLU of one causal depthwise convolution (a sum over
   the taps of shifted products, zeros before the sequence) over their
   concatenated channels; ``q`` and ``k`` divided by
   their norm over a head (``x / sqrt(sum x^2 + 1e-6)``), ``q`` times
   ``d_k^-1/2``. ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
   dt_bias)``, one number a value head. Then **token by token**
   (``lax.scan`` over the sequence, float32, products and sums and no
   matmul), per value head i with ``S`` [keys, values] from zero and the
   keys of head ``i // (h / h_k)``:

       S <- exp(g_t) S;  u = beta_t (v_t - S^T k_t);
       S <- S + k_t u^T;  o_t = S^T q_t.

   The scan runs in blocks of ``scan_block`` tokens, each recomputed in the
   backward pass (one state a block is kept). ``state_dtype`` rounds the
   state after every token and ``key_head_of`` maps a value head to its key
   head: float32 and ``i // groups`` are the configuration's; the others are
   there for the controls of ``tools/scan_check.py``. Output ``(RMSNorm(o)
   w_n SiLU(z)) W_o``, the norm over a head's values, ``w_n`` not
   zero-centred.
2. **Gated attention**, ``kv`` key heads of ``head_dim`` (read off ``k``).
   ``[q; gamma] = h W_q`` per head, ``k = h W_k``, ``v = h W_v``; ``q`` and
   ``k`` under a zero-centred RMSNorm over a head; the first ``rotary`` =
   ``partial_rotary_factor head_dim`` entries of both rotated half against
   half by the row's position at ``rope_theta^(-2j / rotary)`` (``rotate``
   of ``plain_laguna``); causal softmax of ``q k^T head_dim^-1/2`` in
   float32 over all keys in blocks of ``query_block`` queries with the
   group as an axis (``banded_attention`` of ``plain_laguna``, no window),
   the probabilities meeting V in ``dtype``; ``(attn sigmoid(gamma)) W_o``.
3. **Expert layer.** ``p = softmax(h W_r)`` in float32 at the highest
   precision; ``lax.top_k`` over all E; weights ``p_j / sum over the
   chosen``, 0 elsewhere; every HELD expert (``first_expert ..`` as many as
   the tree has) applied to every token, one at a time, times the token's
   weight for it. What the experts held elsewhere would add is left out.
   Plus ``sigmoid(h w_sg)`` times the shared expert, on every token.
   Load-balance loss ``E / (T k) sum_e counts_e mean_t p[t, e]``.
4. Final norm, the untied head, next-token NLL, in blocks of ``head_rows``.

Each half of a layer is recomputed in the backward pass. ``dtype`` is the
matmul operands' (float32 accumulation; float32 residual stream, norms,
gates, decay, state, softmax and router whatever it is): the cell runs this
reference with float32 operands at the highest matmul precision, so the
program's bf16 operands, its chunked scan and its kernels are all held
against one float32 computation; with ``dtype`` bfloat16 the casts are the
program's own and the two differ by the order sums are taken in and by the
algorithm of step 1 alone (token by token here, chunked there).

Returns ``(the per-position negative log-likelihood [rows, s - 1], the mean
over layers of the load-balance loss)``.
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.plain_kimi_linear import (F32, HIGHEST, _matmul, _rms_norm,
                                             _swiglu)
from benchmark.lib.plain_laguna import banded_attention, rotate


def _zero_norm(x, w, eps):
    return _rms_norm(x, 1.0 + w, eps)


def _conv_silu(y, w):
    """y [s, channels] float32, w [taps, channels]: SiLU of the causal
    depthwise convolution, a tap at a time. (``plain_kimi_linear``'s is one
    ``lax.conv_general_dilated``, which over these 8192 channels keeps four
    transposed float32 copies of ``y`` alive at once, 2 GB at 16,384 tokens:
    what stood between this reference and the chip's memory.)"""
    taps, s = w.shape[0], y.shape[0]
    padded = jnp.pad(y, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[t:t + s] * w[t] for t in range(taps)))


def gated_delta_rule(q, k, v, g, beta, *, scan_block, state_dtype=F32,
                     key_head_of=None):
    """Step 1's recurrence for one sequence: q, k [s, h_k, d_k], v [s, h,
    d_v], g, beta [s, h], float32 -> o [s, h, d_v]."""
    s, heads = g.shape
    groups = heads // q.shape[1]
    of = (np.arange(heads) // groups if key_head_of is None
          else np.asarray(key_head_of))
    q, k = q[:, of], k[:, of]           # a value head's own queries and keys
    # the state's rounding as an op of its own: a cast there and back is one
    # the TPU compiler may drop (it keeps excess precision where it can)
    kept = jnp.finfo(state_dtype)

    def token(state, inputs):
        q_t, k_t, v_t, g_t, beta_t = inputs
        state = jnp.exp(g_t)[:, None, None] * state
        u = beta_t[:, None] * (v_t - (state * k_t[:, :, None]).sum(axis=1))
        state = jax.lax.reduce_precision(
            state + k_t[:, :, None] * u[:, None, :], kept.nexp, kept.nmant)
        return state, (state * q_t[:, :, None]).sum(axis=1)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    scan_block = min(scan_block, s)
    state = jnp.zeros((heads, q.shape[-1], v.shape[-1]), F32)
    inputs = tuple(x.reshape(s // scan_block, scan_block, *x.shape[1:])
                   for x in (q, k, v, g, beta))
    return jax.lax.scan(block, state, inputs)[1].reshape(s, heads, -1)


def _gdn(x, p, *, key_dim, dtype, eps, scan_block, state_dtype):
    """One sequence. x [s, d] (the normalised hidden state)."""
    s = x.shape[0]
    heads = p["A_log"].shape[0]
    values = p["o"]["kernel"].shape[0]
    keys = (p["conv"].shape[1] - values) // 2
    key_heads = keys // key_dim

    @jax.checkpoint
    def prepared(qkv, conv, ba, a_log, dt_bias):
        def unit(y):
            return y / jnp.sqrt((y * y).sum(axis=-1, keepdims=True) + 1e-6)

        mixed = _conv_silu(qkv.astype(F32), conv)
        q, k = (mixed[:, i * keys:(i + 1) * keys].reshape(s, key_heads, -1)
                for i in (0, 1))
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, heads:] + dt_bias)
        return (unit(q) * key_dim ** -0.5, unit(k),
                mixed[:, 2 * keys:].reshape(s, heads, -1), g,
                jax.nn.sigmoid(ba[:, :heads]))

    @jax.checkpoint
    def gated(o, z, scale):
        return (_rms_norm(o, scale, eps).reshape(s, -1)
                * jax.nn.silu(z.astype(F32)))

    qkvz = _matmul(x, p["qkvz"]["kernel"], dtype)
    ba = jnp.dot(x.astype(F32), p["ba"]["kernel"], precision=HIGHEST)
    o = jax.checkpoint(partial(
        gated_delta_rule, scan_block=scan_block, state_dtype=state_dtype))(
            *prepared(qkvz[:, :2 * keys + values], p["conv"], ba,
                      p["A_log"], p["dt_bias"]))
    return _matmul(gated(o, qkvz[:, 2 * keys + values:],
                         p["o_norm"]["scale"]), p["o"]["kernel"], dtype)


def _attention(x, p, *, head_dim, rotary, dtype, eps, query_block):
    """One sequence. x [s, d]."""
    s = x.shape[0]
    kv_heads = p["k"]["kernel"].shape[1] // head_dim
    q, gate = jnp.split(_matmul(x, p["q"]["kernel"], dtype).reshape(
        s, kv_heads, -1, 2 * head_dim), 2, axis=-1)
    k, v = (_matmul(x, p[name]["kernel"], dtype).reshape(s, kv_heads,
                                                         head_dim)
            for name in "kv")
    out = banded_attention(
        rotate(_zero_norm(q, p["q_norm"]["scale"], eps), *rotary),
        rotate(_zero_norm(k, p["k_norm"]["scale"], eps), *rotary), v,
        window=None, dtype=dtype, query_block=query_block)
    gated = out.astype(F32) * jax.nn.sigmoid(gate.astype(F32))
    return _matmul(gated.reshape(s, -1), p["o"]["kernel"], dtype)


def gate_weights(x, router, top_k):
    """``([T, E] float32 weights, 0 off the chosen set; the load-balance
    loss)`` of step 3."""
    probs = jax.nn.softmax(jnp.dot(x.astype(F32), router, precision=HIGHEST),
                           axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    chosen = (top_e[:, :, None]
              == jnp.arange(router.shape[1])[None, None, :])    # [T, k, E]
    weights = (chosen * (top_p / top_p.sum(axis=-1, keepdims=True)
                         )[:, :, None]).sum(axis=1)
    counts = jax.lax.stop_gradient(chosen.sum(axis=(0, 1)).astype(F32))
    load_balance = (router.shape[1] / (x.shape[0] * top_k)
                    * (counts * probs.mean(axis=0)).sum())
    return weights, load_balance


def experts(x, p, *, top_k, first_expert, dtype):
    """x [T, d]. ``(the held experts' part of the layer's output plus the
    gated shared expert's, [T, d] float32; the load-balance loss)``."""
    held = p["gate"].shape[0]
    weight, load_balance = gate_weights(x, p["router"], top_k)
    weight = weight[:, first_expert:first_expert + held]

    @jax.checkpoint
    def one_expert(acc, inputs):
        w_gate, w_up, w_down, w_e = inputs
        hidden = (jax.nn.silu(_matmul(x, w_gate, dtype))
                  * _matmul(x, w_up, dtype))
        return acc + w_e[:, None] * _matmul(hidden, w_down, dtype).astype(
            F32), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, F32),
                        (p["gate"], p["up"], p["down"], weight.T))
    shared_gate = jax.nn.sigmoid(
        _matmul(x, p["shared_gate"]["kernel"], dtype).astype(F32))
    return (y.astype(x.dtype) + shared_gate * _swiglu(x, p["shared"], dtype),
            load_balance)


def rotary_of(head_dim, rope_theta, partial_rotary_factor):
    """``(rotary, frequencies [rotary / 2], 1.0)`` as ``rotate`` takes
    them."""
    rotary = int(head_dim * partial_rotary_factor)
    own = float(rope_theta) ** (-2.0 * np.arange(rotary // 2) / rotary)
    return rotary, own.astype(np.float32), 1.0


def causal_lm_nll(params, tokens, *, key_dim, head_dim, rope_theta,
                  partial_rotary_factor, top_k, first_expert, eps, dtype,
                  scan_block=128, query_block=256, head_rows=2048,
                  state_dtype=F32):
    """``(next-token NLL at positions 0..s-2 [rows, s-1], the layers' mean
    load-balance loss)``, untied head."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape
        rotary = rotary_of(head_dim, rope_theta, partial_rotary_factor)

        @jax.checkpoint
        def mixer_half(x, lp):
            h = _zero_norm(x, lp["norm"]["scale"], eps)
            if "gdn" in lp:
                mix = jax.vmap(lambda row: _gdn(
                    row, lp["gdn"], key_dim=key_dim, dtype=dtype, eps=eps,
                    scan_block=scan_block, state_dtype=state_dtype))
            else:
                mix = jax.vmap(lambda row: _attention(
                    row, lp["attn"], head_dim=head_dim, rotary=rotary,
                    dtype=dtype, eps=eps, query_block=query_block))
            return x + mix(h)

        @jax.checkpoint
        def ffn_half(x, lp):
            h = _zero_norm(x, lp["norm"]["scale"], eps)
            y, load_balance = experts(
                h.reshape(rows * s, d), lp["moe"], top_k=top_k,
                first_expert=first_expert, dtype=dtype)
            return x + y.reshape(rows, s, d), load_balance

        layers = sum(name.startswith("layer_") for name in p)
        load_balance = 0.0
        for i in range(layers):
            lp = p[f"layer_{i}"]
            x, aux = ffn_half(mixer_half(x, lp["mixer"]), lp["ffn"])
            load_balance += aux / layers
        x = _zero_norm(x, p["final_norm"]["scale"], eps)
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
    return nll.reshape(rows, s)[:, :-1], load_balance
