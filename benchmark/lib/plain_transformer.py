"""Plain references: the two transformer configurations in jax.numpy.

The forward pass of ``byteps_tpu.models.transformer`` written out over the
same parameter tree, with nothing of the program in it: no flax module, no
pluggable attention, no kernel. The casts are the configuration's own
(bfloat16 matmul inputs, float32 layer norms, softmax and logits), so the
reference and the program differ by the order XLA happens to sum in, not by
a precision. The yardstick keeps its own copy so that a later change to the
model code or to an attention kernel is held to this arithmetic.

Departures of both from their papers (they are the program's, and the
configuration files list them under ``assumed``): pre-LN blocks, tanh GELU,
no dropout; BERT has no token-type embedding, no pooler and an untied
decoder.

``nll`` functions return the per-position negative log-likelihood, so that
the loss is a weighted sum over positions with weights worked out on the
host (``benchmark/lib/reference.py``): linear in rows, and therefore exact
under any split into micro-batches.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6  # flax.linen.LayerNorm's default, which the models use


def _layer_norm(x, p):
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = jnp.maximum(0.0, (x * x).mean(-1, keepdims=True) - mean * mean)
    return (x - mean) * (jax.lax.rsqrt(var + LN_EPS) * p["scale"]) + p["bias"]


def _dense(x, p, dtype, spec="...d,dm->...m"):
    y = jnp.einsum(spec, x.astype(dtype), p["kernel"].astype(dtype))
    return y + p["bias"].astype(dtype)


def _attention(x, p, dtype, causal):
    q, k, v = (_dense(x, p[n], dtype, "bsd,dhk->bshk")
               for n in ("query", "key", "value"))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32)
    s = s * (1.0 / q.shape[-1] ** 0.5)
    if causal:
        n = q.shape[1]
        keep = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        s = jnp.where(keep[None, None], s, jnp.finfo(jnp.float32).min)
    prob = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", prob, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32).astype(dtype)
    return _dense(out, p["out"], dtype, "bshk,hkd->bsd")


def _trunk(p, tokens, num_layers, dtype, causal):
    """Embeddings, ``num_layers`` pre-LN blocks, the final layer norm."""
    tok = p["tok_embed"]["embedding"].astype(dtype)[tokens]
    pos = p["pos_embed"]["embedding"].astype(dtype)[:tokens.shape[1]]
    x = tok + pos[None]
    for i in range(num_layers):
        lp = p[f"layer_{i}"]
        y = _attention(_layer_norm(x, lp["LayerNorm_0"]), lp["attention"],
                       dtype, causal)
        x = x + y.astype(x.dtype)
        y = _dense(_layer_norm(x, lp["LayerNorm_1"]), lp["mlp_in"], dtype)
        y = _dense(jax.nn.gelu(y, approximate=True), lp["mlp_out"], dtype)
        x = x + y.astype(x.dtype)
    return _layer_norm(x, p["final_ln"])


def _nll(logits, targets):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def causal_lm_nll(params, tokens, *, num_layers, dtype):
    """GPT-2: next-token NLL at positions 0..s-2, tied output projection.
    Returns [rows, s-1]."""
    p = params["params"]
    x = _trunk(p, tokens, num_layers, dtype, causal=True)
    embed = p["tok_embed"]["embedding"].astype(dtype)
    logits = jnp.einsum("bsd,vd->bsv", x.astype(dtype), embed)
    return _nll(logits[:, :-1], tokens[:, 1:])


def masked_lm_nll(params, tokens, labels, *, num_layers, dtype):
    """BERT: NLL of ``labels`` at every position (the caller's weights pick
    the masked ones), transform + untied float32 decoder. Returns
    [rows, s]."""
    p = params["params"]
    x = _trunk(p, tokens, num_layers, dtype, causal=False)
    x = jax.nn.gelu(_dense(x, p["mlm_dense"], dtype), approximate=True)
    x = _layer_norm(x, p["mlm_ln"])
    return _nll(_dense(x, p["mlm_out"], jnp.float32), labels)
