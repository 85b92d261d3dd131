"""BERT-Large MLM pre-training, phase 1: the program's model, its batches,
its plain reference and its operations per token. Sizes come from
``bert-large.json`` (``cfg``)."""

import jax.numpy as jnp
import numpy as np


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import TransformerEncoder, masked_lm_loss

    model = TransformerEncoder(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        mlp_dim=cfg["intermediate_size"],
        max_len=cfg["max_position_embeddings"],
        dtype=jnp.dtype(cfg["compute_dtype"]), attn_impl=cfg["attn_impl"])
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    def init(key):
        return model.init(key, example)

    def loss_fn(params, batch):
        return masked_lm_loss(model.apply(params, batch["tokens"]),
                              batch["labels"], batch["mask"])

    return init, loss_fn


def make_batch(cfg, rng, rows):
    """One global batch: uniform tokens over the whole vocabulary; in every
    row round(mask_rate x seq) positions are masked (19 of 128), their
    inputs replaced by [MASK] and their labels the original tokens."""
    seq = cfg["seq_len"]
    labels = rng.integers(0, cfg["vocab_size"], (rows, seq), dtype=np.int32)
    n_masked = max(1, round(cfg["mask_rate"] * seq))
    picks = np.argsort(rng.random((rows, seq)), axis=1)[:, :n_masked]
    mask = np.zeros((rows, seq), np.int32)
    np.put_along_axis(mask, picks, 1, axis=1)
    tokens = np.where(mask == 1, cfg["mask_token_id"], labels).astype(np.int32)
    return {"tokens": tokens, "labels": labels, "mask": mask}


def reference_weights(cfg, batch, shards):
    """The program reports the mean over chips of each chip's own masked
    mean. As a weight per position: mask / (masked count of the row's shard
    x shards) — not the masked mean of the global batch when the shards'
    counts differ."""
    mask = batch["mask"].astype(np.float32)
    per_shard = mask.reshape(shards, -1, mask.shape[1])
    counts = np.maximum(per_shard.sum(axis=(1, 2), keepdims=True), 1.0)
    return (per_shard / (counts * shards)).reshape(mask.shape)


def reference_loss(cfg):
    from benchmark.lib.plain_transformer import masked_lm_nll

    def weighted_loss(params, batch):
        nll = masked_lm_nll(params, batch["tokens"], batch["labels"],
                            num_layers=cfg["num_hidden_layers"],
                            dtype=jnp.dtype(cfg["compute_dtype"]))
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the forward and backward passes need per trained token:
    6 x matmul parameters (12 d^2 per layer at mlp = 4d, the MLM transform
    d^2 and the untied decoder d x vocab, computed at every position as the
    model is built; embedding look-ups are not matmuls) + bidirectional
    attention 12 L s d. Optimizer and recomputed operations do not count."""
    d, m = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    matmul_params = (layers * (4 * d * d + 2 * d * m) + d * d
                     + d * cfg["vocab_size"])
    attention = 12 * layers * cfg["seq_len"] * d
    return 6 * matmul_params + attention
