"""GPT-2 124M: the program's model, its batches, its plain reference and
its operations per token. Sizes come from ``gpt2-124m.json`` (``cfg``)."""

import jax.numpy as jnp
import numpy as np


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import TransformerLM, lm_loss

    model = TransformerLM(
        vocab_size=cfg["vocab_size"], num_layers=cfg["n_layer"],
        d_model=cfg["n_embd"], num_heads=cfg["n_head"],
        mlp_dim=cfg["n_inner"], max_len=cfg["n_positions"],
        dtype=jnp.dtype(cfg["compute_dtype"]), attn_impl=cfg["attn_impl"])
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    def init(key):
        return model.init(key, example)

    def loss_fn(params, batch):
        return lm_loss(model.apply(params, batch["tokens"]), batch["tokens"])

    return init, loss_fn


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences: uniform tokens over the whole
    vocabulary."""
    return {"tokens": rng.integers(0, cfg["vocab_size"],
                                   (rows, cfg["seq_len"]), dtype=np.int32)}


def reference_weights(cfg, batch, shards):
    """lm_loss is the mean over rows x (s-1) positions; every shard has as
    many, so the mean over shards of shard means is the global mean."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    from benchmark.lib.plain_transformer import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(params, batch["tokens"],
                            num_layers=cfg["n_layer"],
                            dtype=jnp.dtype(cfg["compute_dtype"]))
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the forward and backward passes need per trained token:
    6 x matmul parameters (12 d^2 per layer at mlp = 4d, plus the tied
    output projection; embedding look-ups are not matmuls) + attention
    12 L s d, halved because a causal model needs only the lower triangle
    (what the algorithm needs, not what attn_impl="full" computes).
    Optimizer and recomputed operations do not count."""
    d, m, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    matmul_params = layers * (4 * d * d + 2 * d * m) + d * cfg["vocab_size"]
    attention = 12 * layers * cfg["seq_len"] * d // 2
    return 6 * matmul_params + attention
