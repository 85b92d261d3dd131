"""Plain reference: the ZAYA1 decoder in jax.numpy.

The forward pass of ``byteps_tpu.models.zaya`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no
kernel, no sorted permutation, no grouped matmul. A layer is

    a = (s1 * x + b1) + (u1 * CCA(N1(x)) + c1)
    y = (s2 * a + b2) + (u2 * MoE(N2(a), r_prev) + c2),   r to the next layer

with ``N`` the RMSNorm ``x rsqrt(mean x^2 + eps) w``.

1. **CCA, grouped**, ``kv`` key heads of ``head_dim`` = d and ``heads`` query
   heads (read off ``k`` and ``q``), group G = heads / kv. ``q~ = h W_q``,
   ``k~ = h W_k``; ``v = [h W_v1 ; h- W_v2]`` with ``h-`` the rows of ``h``
   one token later and a zero row first: the first half of the key heads
   carry the current token's value, the second half the previous token's.
   ``z = conv1(conv0([q~ ; k~]))``, each convolution **an explicit sum over
   its taps of shifted arrays** (zeros before the sequence) plus a bias a
   channel: conv0 a number a channel and tap, conv1 a [d, d] matrix a head
   and tap. ``q = z_q + (q~[i] + k~[i // G]) / 2``, ``k = z_k + (mean over
   the group of q~ + k~) / 2``, the group as an axis. ``q^ = sqrt(d) q /
   |q|``, ``k^ = sqrt(d) exp(theta_j) k / |k|`` per head in float32 (``x /
   sqrt(sum x^2 + 1e-12)``); the first ``partial_rotary_factor d`` entries
   of both rotated half against half (``rotate`` of ``plain_laguna``);
   causal softmax of ``q^ k^T d^-1/2`` in float32 over all keys in blocks of
   ``query_block`` queries with the group as an axis (``banded_attention``
   of ``plain_laguna``, no window); ``o W_o``.
   ``value_shift``, ``swap_taps``, ``qk_mean``, ``temperature`` and
   ``norm_dtype`` are the configuration's at their defaults; the others are
   there for the controls of ``tools/attention_check.py``.
2. **Expert sublayer.** ``r = g W_down (+ gamma * r_prev`` after the first
   layer), ``l = W3 gelu(W2 gelu(W1 r + beta1) + beta2)`` (erf), float32 at
   the highest precision; ``p = softmax(l)``; ``e* = argmax(p + bal)`` over
   all E; every HELD expert (``first_expert ..`` as many as the tree has)
   applied to every token, one at a time, times ``p[e*]`` where it is the
   token's and 0 where not. What the experts held elsewhere would add is
   left out.
3. Final norm, the head the embedding transposed, next-token NLL, in blocks
   of ``head_rows``.

Each half of a layer is recomputed in the backward pass. ``dtype`` is the
matmul operands' (float32 accumulation; float32 residual stream, norms,
normalisation, rotation, softmax and router whatever it is): the cell runs
this reference with float32 operands at the highest matmul precision.

Returns the per-position negative log-likelihood [rows, s - 1].
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from benchmark.lib.plain_kimi_linear import F32, HIGHEST, _matmul, _rms_norm
from benchmark.lib.plain_laguna import banded_attention, rotate
from benchmark.lib.plain_qwen3_next import rotary_of


def _later(x, n):
    """x [s, ...] ``n`` tokens later: row t holds row t - n, zeros first."""
    return jnp.pad(x, ((n, 0),) + ((0, 0),) * (x.ndim - 1))[:x.shape[0]]


def cca(x, p, *, head_dim, rotary, dtype, query_block, value_shift=True,
        swap_taps=False, qk_mean=True, temperature=True, norm_dtype=F32):
    """Step 1 for one sequence. x [s, D] (the normalised hidden state)."""
    s, d = x.shape[0], head_dim
    heads, kv = (p[name]["kernel"].shape[1] // d for name in "qk")
    group = heads // kv
    q_in, k_in = (_matmul(x, p[name]["kernel"], dtype).astype(F32)
                  for name in "qk")
    v = jnp.concatenate(
        [_matmul(x, p["v1"]["kernel"], dtype),
         _matmul(_later(x, 1) if value_shift else x, p["v2"]["kernel"],
                 dtype)], axis=-1).reshape(s, kv, d)
    z = jnp.concatenate([q_in, k_in], axis=-1)
    taps = p["conv0"].shape[0]
    z = sum(_later(z, taps - 1 - i) * p["conv0"][i]
            for i in range(taps)) + p["conv0_bias"]
    z = z.reshape(s, heads + kv, d)
    w = p["conv1"][::-1] if swap_taps else p["conv1"]
    taps = w.shape[0]
    z = sum(jnp.einsum("shc,hcd->shd", _later(z, taps - 1 - i).astype(dtype),
                       w[i].astype(dtype), preferred_element_type=F32)
            for i in range(taps)) + p["conv1_bias"]
    q, k = z[:, :heads].reshape(s, kv, group, d), z[:, heads:]
    if qk_mean:
        q_in, k_in = q_in.reshape(s, kv, group, d), k_in.reshape(s, kv, d)
        q = q + 0.5 * (q_in + k_in[:, :, None])
        k = k + 0.5 * (q_in.mean(axis=2) + k_in)

    def unit(y):
        if norm_dtype == F32:
            return y / jnp.sqrt((y * y).sum(axis=-1, keepdims=True) + 1e-12)
        # the control: the row, its squares, their running sum, the root and
        # the quotient each rounded to ``norm_dtype`` (as an op of its own:
        # a cast there and back is one the TPU compiler may drop)
        kept = jnp.finfo(norm_dtype)

        def rounded(a):
            return jax.lax.reduce_precision(a, kept.nexp, kept.nmant)

        y, total = rounded(y), 0.0
        for i in range(d):
            total = rounded(total + rounded(y[..., i] * y[..., i]))
        return rounded(y / rounded(jnp.sqrt(total))[..., None])

    tau = jnp.exp(p["temperature"]) if temperature else jnp.ones(kv, F32)
    out = banded_attention(
        rotate(d ** 0.5 * unit(q), *rotary).astype(dtype),
        rotate(d ** 0.5 * tau[:, None] * unit(k), *rotary).astype(dtype), v,
        window=None, dtype=dtype, query_block=query_block)
    return _matmul(out.reshape(s, -1), p["o"]["kernel"], dtype)


def router(g, p, r_prev):
    """``(logits [T, E], r [T, R])`` of step 2, float32."""
    def dense(x, layer):
        y = jnp.dot(x, layer["kernel"], precision=HIGHEST)
        return y + layer["bias"] if "bias" in layer else y

    r = dense(g.astype(F32), p["down"])
    if r_prev is not None:
        r = r + p["depth_decay"] * r_prev
    hidden = r
    for name in ("mlp_1", "mlp_2"):
        hidden = jax.nn.gelu(dense(hidden, p[name]), approximate=False)
    return dense(hidden, p["mlp_3"]), r


def gate_weights(logits, bal):
    """[T, E] float32: ``p[e*]`` at ``e* = argmax(p + bal)``, 0 elsewhere."""
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = jnp.argmax(probs + bal, axis=-1)
    return jnp.where(chosen[:, None] == jnp.arange(logits.shape[1])[None, :],
                     probs, 0.0)


def experts(g, p, r_prev, *, first_expert, dtype):
    """g [T, D]. ``(the held experts' part of the layer's output [T, D]
    float32, r)``."""
    held = p["gate"].shape[0]
    logits, r = router(g, p["router"], r_prev)
    weight = gate_weights(logits, p["select_bias"])[
        :, first_expert:first_expert + held]

    @jax.checkpoint
    def one_expert(acc, inputs):
        w_gate, w_up, w_down, w_e = inputs
        hidden = (jax.nn.silu(_matmul(g, w_gate, dtype))
                  * _matmul(g, w_up, dtype))
        return acc + w_e[:, None] * _matmul(hidden, w_down, dtype).astype(
            F32), None

    y, _ = jax.lax.scan(one_expert, jnp.zeros(g.shape, F32),
                        (p["gate"], p["up"], p["down"], weight.T))
    return y, r


def merge(x, y, p):
    return ((p["stream_scale"] * x + p["stream_bias"])
            + (p["branch_scale"] * y.astype(F32) + p["branch_bias"]))


def causal_lm_nll(params, tokens, *, head_dim, rope_theta,
                  partial_rotary_factor, first_expert, eps, dtype,
                  query_block=256, head_rows=2048):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), tied head."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape
        rotary = rotary_of(head_dim, rope_theta, partial_rotary_factor)

        @jax.checkpoint
        def mixer_half(x, lp):
            h = _rms_norm(x, lp["norm"]["scale"], eps)
            return merge(x, jax.vmap(lambda row: cca(
                row, lp["cca"], head_dim=head_dim, rotary=rotary,
                dtype=dtype, query_block=query_block))(h), lp["merge"])

        @jax.checkpoint
        def ffn_half(a, r_prev, lp):
            g = _rms_norm(a, lp["norm"]["scale"], eps)
            y, r = experts(g.reshape(rows * s, d), lp["moe"], r_prev,
                           first_expert=first_expert, dtype=dtype)
            return merge(a, y.reshape(rows, s, d), lp["merge"]), r

        r = None
        for i in range(sum(name.startswith("layer_") for name in p)):
            lp = p[f"layer_{i}"]
            x, r = ffn_half(mixer_half(x, lp["mixer"]), r, lp["ffn"])
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        # a sequence's last row predicts nothing: it gets token 0 as its
        # target and is dropped, so that the rows divide into even blocks
        targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))

        @jax.checkpoint
        def head(inputs):
            h, target = inputs
            logp = jax.nn.log_softmax(
                _matmul(h, p["embed"]["embedding"].T, dtype).astype(F32))
            return -jnp.take_along_axis(logp, target[:, None], axis=-1)[:, 0]

        block = min(head_rows, s)
        nll = jax.lax.map(head, (x.reshape(-1, block, d),
                                 targets.reshape(-1, block)))
    return nll.reshape(rows, s)[:, :-1]
