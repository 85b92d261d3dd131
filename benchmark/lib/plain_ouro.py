"""Plain reference: the Ouro looped decoder and its objective in jax.numpy.

The forward pass of ``byteps_tpu.models.ouro`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no scan,
a Python loop over the passes and over the layers, so the 4 x L block
applications stand in the program text one after the other and every one
reads the same ``layer_i`` leaves. The exit distribution and its entropy are
the formulas as the paper prints them (products of sigmoids, ``-sum p log
p``), where the program works in logs. The casts are the configuration's own
(``dtype`` matmul operands with float32 accumulation; float32 residual
stream, RMSNorm statistics, rotary tables, attention softmax, exit gate and
logits softmax), so reference and program differ by the order XLA sums in,
not by a precision. In float32 the matmuls run at the highest precision.

What the published ``config.json`` does not say is the family's convention
(``modeling_ouro.py``; the configuration file lists each under ``assumed``):
four RMSNorms a block, the second and the fourth on a sublayer's *output*
before it joins the stream; the one final norm closes every pass and its
output is what the next pass starts from; the exit gate is a biased linear
map to one logit read off that normed state; the last pass takes the
probability the gates left; nothing is detached.

``checkpoint=True`` recomputes each block application and each pass's exit
in the backward pass (``jax.checkpoint``: the same mathematics), so that
the reference step fits the chip at s 4096, where one application's float32
attention scores are 1.07 GB and one exit's logits 0.8 GB.

Returns the per-position ``sum_r p_t(r) nll_t(r) - beta H(p_t)`` (the loss
is a weighted sum over positions, ``benchmark/lib/reference.py``).
"""

from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _matmul(x, w, dtype):
    """``dtype`` operands, float32 accumulation, ``dtype`` result."""
    return jnp.einsum("...d,dm->...m", x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def _rope(x, theta):
    """[rows, s, heads, head_dim], positions 0..s-1, half-split pairs over
    the whole head width."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = (x[..., :half].astype(jnp.float32),
              x[..., half:].astype(jnp.float32))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _attention(x, p, num_heads, dtype, theta):
    rows, s, d = x.shape
    heads = (rows, s, num_heads, d // num_heads)
    q = _rope(_matmul(x, p["q"]["kernel"], dtype).reshape(heads), theta)
    k = _rope(_matmul(x, p["k"]["kernel"], dtype).reshape(heads), theta)
    v = _matmul(x, p["v"]["kernel"], dtype).reshape(heads)
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
    score = score * (1.0 / heads[-1] ** 0.5)
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    score = jnp.where(keep[None, None], score, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(score, axis=-1),
                     v.astype(jnp.float32),
                     preferred_element_type=jnp.float32).astype(dtype)
    return _matmul(out.reshape(rows, s, d), p["o"]["kernel"], dtype)


def _mlp(x, p, dtype):
    hidden = (jax.nn.silu(_matmul(x, p["gate"]["kernel"], dtype))
              * _matmul(x, p["up"]["kernel"], dtype))
    return _matmul(hidden, p["down"]["kernel"], dtype)


def block(x, lp, *, num_heads, eps, rope_theta, dtype):
    """a = x + N2(Attn(N1(x))); y = a + N4(MLP(N3(a)))."""
    a = x + _rms_norm(
        _attention(_rms_norm(x, lp["attn_norm"]["scale"], eps), lp,
                   num_heads, dtype, rope_theta),
        lp["attn_post_norm"]["scale"], eps)
    return a + _rms_norm(
        _mlp(_rms_norm(a, lp["mlp_norm"]["scale"], eps), lp["mlp"], dtype),
        lp["mlp_post_norm"]["scale"], eps)


def exit_of(h, head, gate, tokens, dtype):
    """One pass's exit: (next-token NLL [rows, s-1], lambda [rows, s-1])
    off the normed state ``h``; the last position predicts nothing."""
    logits = _matmul(h, head["kernel"], dtype)
    logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    logit = jnp.dot(h[:, :-1], gate["kernel"][:, 0],
                    precision=jax.lax.Precision.HIGHEST) + gate["bias"][0]
    return nll, jax.nn.sigmoid(logit)


def passes(params, tokens, *, num_layers, num_passes, num_heads, eps,
           rope_theta, dtype, checkpoint=False, untied=False):
    """The normed state after each pass: ``num_passes`` of [rows, s, d].
    ``untied``: the model with ``num_passes x num_layers`` separately
    parametrised blocks, pass r reading ``layer_{r L + i}`` — what the
    shared block's gradient must be the sum over."""
    p = params["params"]
    apply = partial(block, num_heads=num_heads, eps=eps,
                    rope_theta=rope_theta, dtype=dtype)
    if checkpoint:
        apply = jax.checkpoint(apply)
    x = p["embed"]["embedding"][tokens]            # float32 residual stream
    states = []
    for r in range(num_passes):
        for i in range(num_layers):
            lp = p[f"layer_{r * num_layers + i if untied else i}"]
            x = apply(x, lp)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        states.append(x)                  # and the next pass starts from it
    return states


def looped_lm_loss_per_position(params, tokens, *, num_layers, num_passes,
                                num_heads, eps, rope_theta, beta, dtype,
                                checkpoint=False, untied=False):
    """[rows, s-1]: sum_r p_t(r) nll_t(r) - beta H(p_t) with p_t(r) =
    lambda_r prod_{j<r} (1 - lambda_j) for r < R and p_t(R) = prod_{j<R}
    (1 - lambda_j)."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        leave = partial(exit_of, dtype=dtype)
        if checkpoint:
            leave = jax.checkpoint(leave)
        exits = [leave(h, p["lm_head"], p["exit_gate"], tokens)
                 for h in passes(params, tokens, num_layers=num_layers,
                                 num_passes=num_passes, num_heads=num_heads,
                                 eps=eps, rope_theta=rope_theta, dtype=dtype,
                                 checkpoint=checkpoint, untied=untied)]
        left, expected, entropy = 1.0, 0.0, 0.0
        for r, (nll, lam) in enumerate(exits):
            prob = left * lam if r < num_passes - 1 else left
            expected = expected + prob * nll
            entropy = entropy - prob * jnp.log(prob)
            left = left * (1.0 - lam)
    return expected - beta * entropy
