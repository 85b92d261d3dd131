"""Plain reference: the SambaY decoder (Phi-4-mini-flash-reasoning) in
jax.numpy.

The forward pass of ``byteps_tpu.models.phi4_flash`` written out over the
same parameter tree, with nothing of the program in it: no flax module, no
chunked scan, no kernel, no ``full_attention`` (the convolution is
``plain_nemotron_h``'s, a sum over the taps). Published layer ``l`` is the
parameters ``layer_<l>_mixer`` and ``layer_<l>_mlp``; the residual stream is
float32:

    h <- h + Mixer_l(LN(h));   h <- h + W_2 (up . SiLU(gate)),
    [gate; up] = LN'(h) W_1

with LN a LayerNorm with weight and bias. Which mixer a layer has is read
off its parameters and its index (``depth`` is the published depth, whose
half is the last Mamba layer):

1. **Mamba-1** (``ssm``). ``[x; z] = u W_in``; ``x`` = SiLU of the causal
   depthwise convolution (a sum over the taps of shifted products, zeros
   before the sequence) plus its bias; ``[delta; B; C] = x W_x``; ``Delta =
   softplus(delta W_Delta + b_Delta)`` in float32 at the highest precision;
   ``A = -exp(A_log)`` [channels, n]. Then **token by token** (``lax.scan``
   over the sequence, float32, products and sums and no matmul) with ``S``
   [channels, n] from zero:

       S <- exp(Delta_t[:, None] A) S + (Delta_t x_t)[:, None] B_t[None, :]
       y_t = S C_t + D x_t

   in blocks of ``scan_block`` tokens, each recomputed in the backward pass.
   ``state_dtype`` rounds the state after every token, ``decay_floor`` clamps
   the log-decay cumulated inside chunks of ``floor_chunk`` tokens from below
   and ``per_channel`` replaces ``A`` by its mean over the state entries:
   float32, None and False are the configuration's;
   the others are there for the controls of ``tools/scan_check.py``. Output
   ``W_out (y SiLU(z))``. Layer ``depth / 2`` hands on ``m = y``, rounded to
   ``dtype``.
2. **Differential attention** (``attn``). The projections' columns by
   halves: of the ``2 P`` query heads, heads ``0 .. P - 1`` are the pairs'
   ``q1`` and the rest their ``q2``; of the ``2 K`` key heads the first ``K``
   are ``k1``, the rest ``k2``, and the value heads likewise ``v1``, ``v2``;
   pair i reads key pair ``i // (P / K)``. For every pair, **two softmax
   maps written out**, float32 logits over all keys at or before the query
   (the last ``window`` of them in a layer below ``depth / 2``), a block of
   ``query_block`` queries at a time:

       o_i = (softmax(q1 k1^T / sqrt(d)) - lambda softmax(q2 k2^T / sqrt(d)))
             [v1; v2]
       lambda = exp(l_q1 . l_k1) - exp(l_q2 . l_k2) + lambda_init
       lambda_init = 0.8 - 0.6 exp(-0.3 l)            (l the published index)
       o_i <- o_i rsqrt(mean o_i^2 + eps) w (1 - lambda_init)

   then ``W_o``. Layer ``depth / 2 + 1`` hands on its K and V; a layer
   without ``k`` in its parameters is a cross layer and reads those.
3. **Gated Memory Unit** (``gmu``): ``W_2 (m . SiLU(x W_1))``.
4. Final LayerNorm, the embedding transposed as the head, next-token NLL,
   in blocks of ``head_rows``.

The handed tensors are plain variables of the layer loop. Every layer is
recomputed in the backward pass. ``dtype`` is the matmul operands' (float32
accumulation): the cell runs this reference with float32 operands at the
highest matmul precision.

Returns the per-position negative log-likelihood [rows, s - 1].
"""

from __future__ import annotations

import contextlib
import math
import re
from functools import partial

import jax
import jax.numpy as jnp

from benchmark.lib.plain_kimi_linear import F32, HIGHEST
from benchmark.lib.plain_nemotron_h import conv_silu


def _matmul(x, w, dtype):
    """``dtype`` operands, float32 accumulation and result."""
    return jnp.einsum("...d,dm->...m", x.astype(dtype), w.astype(dtype),
                      preferred_element_type=F32)


def layer_norm(x, p, eps):
    x = x.astype(F32)
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def selective_scan(x, dt, a, b, c, *, scan_block, state_dtype=F32,
                   decay_floor=None, floor_chunk=16, per_channel=False):
    """Step 1's recurrence for one sequence, without the skip: x, dt [s,
    channels], a [channels, n], b, c [s, n], float32 -> y [s, channels]."""
    s, channels = x.shape
    if per_channel:
        a = jnp.broadcast_to(a.mean(-1, keepdims=True), a.shape)
    # the state's rounding as an op of its own: a cast there and back is one
    # the TPU compiler may drop (it keeps excess precision where it can)
    kept = jnp.finfo(state_dtype)

    def written(state, log_decay, x_t, dt_t, b_t, c_t):
        state = jax.lax.reduce_precision(
            jnp.exp(log_decay) * state + (dt_t * x_t)[:, None] * b_t[None, :],
            kept.nexp, kept.nmant)
        return state, (state * c_t[None, :]).sum(-1)

    def token(state, inputs):
        x_t, dt_t, b_t, c_t, _ = inputs
        return written(state, dt_t[:, None] * a, x_t, dt_t, b_t, c_t)

    def floored(carry, inputs):
        """The control: what a form computes that cumulates the log-decay
        inside chunks of ``floor_chunk`` tokens and clamps the sum at
        ``decay_floor`` — a token's decay is the clamped sums' step."""
        state, total, before = carry
        x_t, dt_t, b_t, c_t, first = inputs
        total = jnp.where(first, 0.0, total) + dt_t[:, None] * a
        clamped = jnp.maximum(total, decay_floor)
        state, y_t = written(state, clamped - jnp.where(first, 0.0, before),
                             x_t, dt_t, b_t, c_t)
        return (state, total, clamped), y_t

    @jax.checkpoint
    def block(carry, inputs):
        return jax.lax.scan(token if decay_floor is None else floored, carry,
                            inputs)

    scan_block = min(scan_block, s)
    inputs = tuple(t.reshape(s // scan_block, scan_block, *t.shape[1:])
                   for t in (x, dt, b, c, jnp.arange(s) % floor_chunk == 0))
    zero = jnp.zeros(a.shape, F32)
    return jax.lax.scan(block, zero if decay_floor is None else (zero,) * 3,
                        inputs)[1].reshape(s, channels)


def _mamba(u, p, *, dtype, scan_block, **controls):
    """One sequence. u [s, d] (the normalised hidden state) -> (the
    mixer's output, y [s, channels] with the skip and before the gate)."""
    inner, n = p["A_log"].shape
    rank = p["dt_proj"].shape[0]
    xz = _matmul(u, p["in"]["kernel"], dtype).astype(dtype)
    x = conv_silu(xz[:, :inner].astype(F32), p["conv"], p["conv_bias"])
    dbc = _matmul(x, p["x_proj"], dtype)
    dt = jax.nn.softplus(jnp.dot(dbc[:, :rank], p["dt_proj"],
                                 precision=HIGHEST) + p["dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(p["A_log"]), dbc[:, rank:rank + n],
                       dbc[:, rank + n:], scan_block=scan_block,
                       **controls) + p["D"] * x
    gated = y * jax.nn.silu(xz[:, inner:].astype(F32))
    return _matmul(gated, p["out"]["kernel"], dtype), y


def two_maps(q, k, v, *, window, dtype, query_block):
    """q [s, 2, K, G, d] (map, key pair, the pairs it serves), k, v [s, 2,
    K, d] -> (a1, a2) [s, K, G, 2 d] each: map j's softmax over the keys at
    or before the query (the last ``window``) times ``[v1; v2]``."""
    s = q.shape[0]
    block = min(query_block, s)
    padded = -(-s // block) * block
    wide = jnp.concatenate([v[:, 0], v[:, 1]], axis=-1)      # [s, K, 2 d]

    @jax.checkpoint
    def one_block(inputs):
        q_b, positions = inputs
        logits = jnp.einsum("qjkgd,sjkd->jkgqs", q_b.astype(dtype),
                            k.astype(dtype), preferred_element_type=F32
                            ) * q.shape[-1] ** -0.5
        back = positions[:, None] - jnp.arange(s)[None, :]
        seen = back >= 0
        if window is not None:
            seen &= back < window
        probs = jax.nn.softmax(
            jnp.where(seen, logits, jnp.finfo(F32).min), axis=-1)
        return jnp.einsum("jkgqs,skd->jqkgd", probs.astype(dtype),
                          wide.astype(dtype), preferred_element_type=F32)

    out = jax.lax.map(one_block, (
        jnp.pad(q, ((0, padded - s),) + ((0, 0),) * 4).reshape(
            padded // block, block, *q.shape[1:]),
        jnp.arange(padded).reshape(-1, block)))
    # [blocks, 2, block, K, G, 2 d] -> two of [s, K, G, 2 d]
    out = jnp.moveaxis(out, 1, 0).reshape(2, padded, *out.shape[3:])[:, :s]
    return out[0], out[1]


def lambda_init(index: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def _attention(x, p, handed, *, index, head_dim, window, dtype, eps,
               query_block):
    """One sequence. x [s, d]; ``handed``: (K, V) [s, key heads, head_dim]
    for a cross layer. Returns (the mixer's output, (K, V) as read)."""
    s = x.shape[0]
    if "k" in p:
        handed = tuple(_matmul(x, p[name]["kernel"], dtype).astype(dtype)
                       .reshape(s, -1, head_dim) for name in "kv")
    k, v = (t.reshape(s, 2, -1, head_dim) for t in handed)
    kv_pairs = k.shape[2]
    q = _matmul(x, p["q"]["kernel"], dtype).astype(dtype).reshape(
        s, 2, kv_pairs, -1, head_dim)
    a1, a2 = two_maps(q, k, v, window=window, dtype=dtype,
                      query_block=query_block)
    init = lambda_init(index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + init)
    o = a1 - lam * a2
    o = (o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
         * p["subln"] * (1.0 - init))
    return _matmul(o.reshape(s, -1), p["o"]["kernel"], dtype), handed


def _gmu(x, p, m, dtype):
    gate = jax.nn.silu(_matmul(x, p["in"]["kernel"], dtype).astype(dtype)
                       .astype(F32))
    return _matmul(m.astype(F32) * gate, p["out"]["kernel"], dtype)


def _swiglu(x, p, dtype):
    gate_up = _matmul(x, p["gate_up"]["kernel"], dtype).astype(dtype)
    half = gate_up.shape[-1] // 2
    return _matmul(gate_up[..., half:] * jax.nn.silu(gate_up[..., :half]),
                   p["down"]["kernel"], dtype)


def causal_lm_nll(params, tokens, *, depth, head_dim, window, eps, dtype,
                  scan_block=128, query_block=256, head_rows=2048,
                  **controls):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), tied head.
    ``depth``: the published number of layers."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        embedding = p["embed"]["embedding"]
        h = embedding[tokens]                      # float32 residual stream
        rows, s, d = h.shape
        held = sorted(int(re.fullmatch(r"layer_(\d+)_mixer", name).group(1))
                      for name in p if name.endswith("_mixer"))

        @partial(jax.checkpoint, static_argnums=(4,))
        def mixer(h, lp, m, kv, index):
            x = layer_norm(h, lp["norm"], eps)
            if "ssm" in lp:
                out, y = jax.vmap(lambda row: _mamba(
                    row, lp["ssm"], dtype=dtype, scan_block=scan_block,
                    **controls))(x)
                if index == depth // 2:
                    m = y.astype(dtype)
            elif "gmu" in lp:
                out = _gmu(x, lp["gmu"], m, dtype)
            else:
                out, read = jax.vmap(lambda row, *handed: _attention(
                    row, lp["attn"], handed, index=index, head_dim=head_dim,
                    window=window if index < depth // 2 else None,
                    dtype=dtype, eps=eps, query_block=query_block))(
                        x, *(kv if "k" not in lp["attn"] else ()))
                if index == depth // 2 + 1:
                    kv = read
            return h + out.astype(F32), m, kv

        @jax.checkpoint
        def mlp(h, lp):
            return h + _swiglu(layer_norm(h, lp["norm"], eps), lp["mlp"],
                               dtype).astype(F32)

        m = kv = None
        for index in held:
            h, m, kv = mixer(h, p[f"layer_{index}_mixer"], m, kv, index)
            h = mlp(h, p[f"layer_{index}_mlp"])
        h = layer_norm(h, p["final_norm"], eps)
        # a sequence's last row predicts nothing: it gets token 0 as its
        # target and is dropped, so that the rows divide into even blocks
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))

        @jax.checkpoint
        def head(inputs):
            x, target = inputs
            logp = jax.nn.log_softmax(_matmul(x, embedding.T, dtype))
            return -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]

        block = min(head_rows, s)
        nll = jax.lax.map(head, (h.reshape(-1, block, d),
                                 targets.reshape(-1, block)))
    return nll.reshape(rows, s)[:, :-1]
