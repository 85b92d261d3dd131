"""Plain reference: the OLMoE decoder in jax.numpy.

The forward pass of ``byteps_tpu.models.olmoe`` written out over the same
parameter tree, with nothing of the program in it: no flax module, no sorted
permutation, no grouped matmul. Every expert is applied to every token, one
expert at a time (a scan, so that the step fits the chip), and its output is
multiplied by the token's router probability if the expert is among the
token's top k and by zero if not: what "each token is processed by its k
experts" means, with no token dropped. The casts are the configuration's own
(``dtype`` matmul operands with float32 accumulation; float32 residual
stream, RMSNorm statistics, rotary tables, attention softmax, router,
combine and logits softmax), so reference and program differ by the order XLA sums in, not by
a precision. In float32 the matmuls run at the highest precision.

Departures from the published model (they are the program's; the
configuration file lists them under ``assumed``): the load-balancing loss is
normalised to 1 at uniform routing (E / (T k) sum_e count_e mean_t p_te,
megablocks' form; Hugging Face's is k times it), both auxiliary losses are
statistics of the tokens handed in (one chip's batch) averaged over layers,
and the training weights 0.01 / 0.001 come from the paper, not from
``config.json``. Rotary embedding is the half-split form of the published
code; Q and K are RMS-normalised over the whole projected width before the
split into heads, as published.

Returns the per-position negative log-likelihood (the loss is a weighted
sum over positions, ``benchmark/lib/reference.py``) and the two auxiliary
losses, which are not sums over positions: the configuration's
``reference_loss`` adds them per shard.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _matmul(x, w, dtype, spec="...d,dm->...m"):
    """``dtype`` operands, float32 accumulation, ``dtype`` result."""
    return jnp.einsum(spec, x.astype(dtype), w.astype(dtype),
                      preferred_element_type=jnp.float32).astype(dtype)


def _rope(x, theta):
    """[rows, s, heads, head_dim], positions 0..s-1, half-split pairs."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = (x[..., :half].astype(jnp.float32),
              x[..., half:].astype(jnp.float32))
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _attention(x, p, num_heads, dtype, eps, theta):
    rows, s, d = x.shape
    heads = (rows, s, num_heads, d // num_heads)
    q = _rms_norm(_matmul(x, p["q"]["kernel"], dtype), p["q_norm"]["scale"],
                  eps)
    k = _rms_norm(_matmul(x, p["k"]["kernel"], dtype), p["k_norm"]["scale"],
                  eps)
    v = _matmul(x, p["v"]["kernel"], dtype).reshape(heads)
    q, k = _rope(q.reshape(heads), theta), _rope(k.reshape(heads), theta)
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)
    score = score * (1.0 / heads[-1] ** 0.5)
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    score = jnp.where(keep[None, None], score, jnp.finfo(jnp.float32).min)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(score, axis=-1),
                     v.astype(jnp.float32),
                     preferred_element_type=jnp.float32).astype(dtype)
    return _matmul(out.reshape(rows, s, d), p["o"]["kernel"], dtype)


def _experts(x, p, top_k, dtype):
    """x: [T, d]. Returns (y [T, d] in x's dtype, load_balance, z_loss)."""
    t, e = x.shape[0], p["router"].shape[1]
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])                      # [T, E]
    _, chosen = jax.lax.top_k(probs, top_k)                     # [T, k]
    mask = (chosen[:, :, None] == jnp.arange(e)[None, None, :]).any(axis=1)
    weight = jnp.where(mask, probs, 0.0)        # raw, not renormalised

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
    return y.astype(x.dtype), load_balance, jnp.mean(lse * lse)


def causal_lm_nll_and_aux(params, tokens, *, num_layers, num_heads, top_k,
                          eps, rope_theta, dtype):
    """Next-token NLL at positions 0..s-2 ([rows, s-1]), untied head, and
    the load-balancing loss and router z-loss over ``tokens``' rows x s
    tokens, each the mean over the layers."""
    precision = (jax.default_matmul_precision("highest")
                 if dtype == jnp.float32 else contextlib.nullcontext())
    with precision:
        p = params["params"]
        x = p["embed"]["embedding"][tokens]        # float32 residual stream
        rows, s, d = x.shape
        load_balance = z_loss = 0.0
        for i in range(num_layers):
            lp = p[f"layer_{i}"]
            x = x + _attention(
                _rms_norm(x, lp["attn_norm"]["scale"], eps), lp["attn"],
                num_heads, dtype, eps, rope_theta)
            y, lb, z = _experts(
                _rms_norm(x, lp["moe_norm"]["scale"], eps).reshape(
                    rows * s, d), lp["moe"], top_k, dtype)
            x = x + y.reshape(rows, s, d)
            load_balance, z_loss = (load_balance + lb / num_layers,
                                    z_loss + z / num_layers)
        x = _rms_norm(x, p["final_norm"]["scale"], eps)
        logits = _matmul(x, p["lm_head"]["kernel"], dtype)
        logp = jax.nn.log_softmax(logits[:, :-1].astype(jnp.float32),
                                  axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return nll, load_balance, z_loss
