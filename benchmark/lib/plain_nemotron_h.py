"""Plain reference: the Nemotron-H decoder in jax.numpy.

The forward pass of ``byteps_tpu.models.nemotron_h`` written out over the
same parameter tree, with nothing of the program in it: no flax module, no
chunked scan, no kernel, no sorted permutation, no grouped matmul. A layer
is ONE mixer under one RMSNorm, ``x <- x + Mixer(N(x))``; which mixer a
layer has is read off its parameters (``ssm``, ``moe`` or ``attn``).

1. **Mamba-2**, ``h`` heads of ``p`` channels over ``G`` groups of ``n``
   state entries (read off ``A_log``, ``out`` and the convolution's width).
   ``[z; xBC] = h W_in`` (``dtype`` operands), ``dt = h W_dt`` in float32 at
   the highest precision. ``xBC`` = SiLU of one causal depthwise convolution
   (a sum over the taps of shifted products, zeros before the sequence) plus
   its bias; split into ``x`` [s, h, p], ``B`` and ``C`` [s, G, n]. ``Delta
   = softplus(dt + dt_bias)``, ``g = -exp(A_log) Delta``. Then **token by
   token** (``lax.scan`` over the sequence, float32, products and sums and no
   matmul), per head i with ``S`` [n, p] from zero and the ``B``, ``C`` of
   group ``i // (h / G)``:

       S <- exp(g_t) S + B_t (Delta_t x_t)^T;   y_t = S^T C_t.

   The scan runs in blocks of ``scan_block`` tokens, each recomputed in the
   backward pass (one state a block is kept). ``state_dtype`` rounds the
   state after every token and ``group_of`` maps a head to its group:
   float32 and ``i // (h / G)`` are the configuration's; the others are
   there for the controls of ``tools/scan_check.py``. Output ``W_out GN((y
   + D x) SiLU(z))``: the gate first, then an RMSNorm over each group's
   channels times one weight vector.
2. **Attention**, ``kv`` key heads of ``head_dim`` (read off ``k``), the
   query heads in groups over them. No rotation, no norm of q or k; causal
   softmax of ``q k^T head_dim^-1/2`` in float32 over all keys in blocks of
   ``query_block`` queries (``banded_attention`` of ``plain_laguna``, no
   window), the probabilities meeting V in ``dtype``.
3. **Expert layer.** Scores ``sigmoid(h W_r)`` in float32 at the highest
   precision; the ``top_k`` largest of ``scores + bias`` chosen; weights the
   chosen scores over their sum (+ 1e-20) times ``routed_scale``, 0
   elsewhere (``gate_weights`` of ``plain_kimi_linear``); every HELD expert
   (``first_expert ..`` as many as the tree has), ``W_down relu(W_up h)^2``,
   applied to every token, one at a time, times the token's weight for it.
   What the experts held elsewhere would add is left out. Plus the shared
   expert of the same body on every token.
4. Final norm, the untied head, next-token NLL, in blocks of ``head_rows``.

Every layer is recomputed in the backward pass. ``dtype`` is the matmul
operands' (float32 accumulation; float32 residual stream, norms, gates,
decay, state, softmax and router whatever it is): the cell runs this
reference with float32 operands at the highest matmul precision.

Returns the per-position negative log-likelihood [rows, s - 1].
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib.plain_kimi_linear import (F32, HIGHEST, _matmul, _rms_norm,
                                             gate_weights)
from benchmark.lib.plain_laguna import banded_attention


def conv_silu(y, w, bias):
    """y [s, channels] float32, w [taps, channels], bias [channels]: SiLU
    of the causal depthwise convolution plus its bias, a tap at a time."""
    taps, s = w.shape[0], y.shape[0]
    padded = jnp.pad(y, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[t:t + s] * w[t] for t in range(taps))
                       + bias)


def selective_scan(c, b, x, g, dt, *, scan_block, state_dtype=F32,
                   group_of=None):
    """Step 1's recurrence for one sequence: c, b [s, G, n], x [s, h, p],
    g, dt [s, h], float32 -> y [s, h, p]."""
    s, heads = g.shape
    of = (np.arange(heads) // (heads // c.shape[1]) if group_of is None
          else np.asarray(group_of))
    # the state's rounding as an op of its own: a cast there and back is one
    # the TPU compiler may drop (it keeps excess precision where it can)
    kept = jnp.finfo(state_dtype)

    def token(state, inputs):
        c_t, b_t, x_t, g_t, dt_t = inputs
        c_t, b_t = c_t[of], b_t[of]     # a head's own C and B
        state = jax.lax.reduce_precision(
            jnp.exp(g_t)[:, None, None] * state
            + b_t[:, :, None] * (dt_t[:, None] * x_t)[:, None, :],
            kept.nexp, kept.nmant)
        return state, (state * c_t[:, :, None]).sum(axis=1)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    scan_block = min(scan_block, s)
    state = jnp.zeros((heads, c.shape[-1], x.shape[-1]), F32)
    inputs = tuple(t.reshape(s // scan_block, scan_block, *t.shape[1:])
                   for t in (c, b, x, g, dt))
    return jax.lax.scan(block, state, inputs)[1].reshape(s, heads, -1)


def gated_group_norm(y, z, weight, groups, eps):
    """``GN(y SiLU(z)) weight``: y, z [s, channels]; the gate first, then an
    RMSNorm over each of the ``groups`` groups of channels."""
    gated = (y * jax.nn.silu(z.astype(F32))).reshape(y.shape[0], groups, -1)
    return (gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + eps)).reshape(
            y.shape) * weight


def _mamba(x, p, *, state_size, dtype, eps, scan_block, state_dtype):
    """One sequence. x [s, d] (the normalised hidden state)."""
    s = x.shape[0]
    heads = p["A_log"].shape[0]
    inner = p["out"]["kernel"].shape[0]
    groups = (p["conv"].shape[1] - inner) // (2 * state_size)
    bc = groups * state_size

    @jax.checkpoint
    def prepared(xbc, conv, conv_bias, dt, a_log, dt_bias):
        mixed = conv_silu(xbc.astype(F32), conv, conv_bias)
        c_in, b_in = (mixed[:, inner + i * bc:inner + (i + 1) * bc].reshape(
            s, groups, state_size) for i in (1, 0))
        step = jax.nn.softplus(dt + dt_bias)
        return (c_in, b_in, mixed[:, :inner].reshape(s, heads, -1),
                -jnp.exp(a_log) * step, step)

    @jax.checkpoint
    def gated(y, x_in, z, skip, weight):
        return gated_group_norm(
            (y + skip[:, None] * x_in).reshape(s, inner), z, weight, groups,
            eps)

    zxbc = _matmul(x, p["in"]["kernel"], dtype)
    dt = jnp.dot(x.astype(F32), p["dt"]["kernel"], precision=HIGHEST)
    operands = prepared(zxbc[:, inner:], p["conv"], p["conv_bias"], dt,
                        p["A_log"], p["dt_bias"])
    y = jax.checkpoint(partial(
        selective_scan, scan_block=scan_block, state_dtype=state_dtype))(
            *operands)
    return _matmul(gated(y, operands[2], zxbc[:, :inner], p["D"], p["norm"]),
                   p["out"]["kernel"], dtype)


def _attention(x, p, *, head_dim, dtype, query_block):
    """One sequence. x [s, d]."""
    s = x.shape[0]
    kv_heads = p["k"]["kernel"].shape[1] // head_dim
    q = _matmul(x, p["q"]["kernel"], dtype).reshape(s, kv_heads, -1,
                                                    head_dim)
    k, v = (_matmul(x, p[name]["kernel"], dtype).reshape(s, kv_heads,
                                                         head_dim)
            for name in "kv")
    out = banded_attention(q, k, v, window=None, dtype=dtype,
                           query_block=query_block)
    return _matmul(out.reshape(s, -1), p["o"]["kernel"], dtype)


def _relu2(x, w_up, w_down, dtype):
    return _matmul(jnp.square(jax.nn.relu(_matmul(x, w_up, dtype))), w_down,
                   dtype)


def experts(x, p, *, top_k, first_expert, routed_scale, dtype):
    """x [T, d]. The held experts' part of the layer's output plus the
    shared expert's, [T, d] in x's dtype."""
    held = p["up"].shape[0]
    weight = gate_weights(x, p["router"], p["select_bias"], top_k,
                          routed_scale)[:, first_expert:first_expert + held]

    @jax.checkpoint
    def one_expert(acc, inputs):
        w_up, w_down, w_e = inputs
        return acc + w_e[:, None] * _relu2(x, w_up, w_down, dtype).astype(
            F32), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(x.shape, F32),
                        (p["up"], p["down"], weight.T))
    return y.astype(x.dtype) + _relu2(x, p["shared"]["up"]["kernel"],
                                      p["shared"]["down"]["kernel"], dtype)


def causal_lm_nll(params, tokens, *, state_size, head_dim, top_k,
                  first_expert, routed_scale, eps, dtype, scan_block=128,
                  query_block=256, head_rows=2048, state_dtype=F32):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), untied head."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape

        @jax.checkpoint
        def layer(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            if "moe" in lp:
                return x + experts(
                    h.reshape(rows * s, d), lp["moe"], top_k=top_k,
                    first_expert=first_expert, routed_scale=routed_scale,
                    dtype=dtype).reshape(rows, s, d)
            if "ssm" in lp:
                mix = jax.vmap(lambda row: _mamba(
                    row, lp["ssm"], state_size=state_size, dtype=dtype,
                    eps=eps, scan_block=scan_block, state_dtype=state_dtype))
            else:
                mix = jax.vmap(lambda row: _attention(
                    row, lp["attn"], head_dim=head_dim, dtype=dtype,
                    query_block=query_block))
            return x + mix(h)

        for i in range(sum(name.startswith("layer_") for name in p)):
            x = layer(x, p[f"layer_{i}"])
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
