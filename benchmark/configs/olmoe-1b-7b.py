"""OLMoE-1B-7B cut to one layer: the program's model, its batches, its plain
reference and its operations per token. Sizes come from ``olmoe-1b-7b.json``
(``cfg``)."""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them: the ``moe``
# reader's probe (``layers/moe.py::setup``) routes that batch through the
# weights of that seed. ``lib/cell.py`` hands a reader neither.
FIRST = {}


def _model(cfg):
    from byteps_tpu.models import OlmoeModel

    return OlmoeModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_experts=cfg["num_experts"], top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["intermediate_size"],
        dtype=jnp.dtype(cfg["compute_dtype"]), attn_impl=cfg["attn_impl"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import olmoe_loss

    model = _model(cfg)
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    def init(key):
        return model.init(key, example)

    def loss_fn(params, batch):
        return olmoe_loss(model.apply(params, batch["tokens"]),
                          batch["tokens"],
                          load_balance_weight=cfg["load_balance_weight"],
                          z_loss_weight=cfg["z_loss_weight"])

    return init, loss_fn


def expert_counts(cfg):
    """``counts(key, tokens) -> [layers, experts]`` assignments per expert
    with the weights of ``key``: the model applied with ``"moe_stats"``
    mutable, under one jit that returns the counts alone, so the compiler
    drops the experts, the head and their weights."""
    import jax

    model = _model(cfg)
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    @jax.jit
    def counts(key, tokens):
        _, stats = model.apply(model.init(key, example), tokens,
                               mutable=["moe_stats"])
        return jnp.stack(jax.tree_util.tree_leaves(stats["moe_stats"]))

    return counts


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences: uniform tokens over the whole
    vocabulary."""
    batch = {"tokens": rng.integers(0, cfg["vocab_size"],
                                    (rows, cfg["seq_len"]), dtype=np.int32)}
    if not FIRST:
        FIRST.update(seed=rng.bit_generator.seed_seq.entropy,
                     tokens=batch["tokens"])
    return batch


def reference_weights(cfg, batch, shards):
    """[shards, rows per shard, s-1], summing to 1: the cross-entropy is the
    mean over rows x (s-1) positions, and every shard has as many. The
    leading axis says which rows a chip holds: the auxiliary losses are
    statistics of one chip's tokens, not sums over positions, so the
    reference works them out per shard and averages."""
    rows, seq = batch["tokens"].shape
    return np.full((shards, rows // shards, seq - 1),
                   1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    from benchmark.lib.plain_olmoe import causal_lm_nll_and_aux

    def weighted_loss(params, batch):
        weight = batch["weight"]
        shards, rows = weight.shape[:2]
        tokens = batch["tokens"].reshape(shards, rows, -1)
        total = 0.0
        for i in range(shards):
            nll, load_balance, z_loss = causal_lm_nll_and_aux(
                params, tokens[i], num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                top_k=cfg["num_experts_per_tok"], eps=cfg["rms_norm_eps"],
                rope_theta=float(cfg["rope_theta"]),
                dtype=jnp.dtype(cfg["compute_dtype"]))
            total = total + (nll * weight[i]).sum() + (
                cfg["load_balance_weight"] * load_balance
                + cfg["z_loss_weight"] * z_loss) / shards
        return total

    return weighted_loss


def flops_per_token(cfg):
    """Operations the forward and backward passes need per trained token:
    6 x matmul parameters a token meets + attention 12 L s d, halved because
    a causal model needs only the lower triangle. Per layer a token meets
    4 d^2 (Q, K, V, O), d E (the router) and the k ACTIVE experts' 3 d m
    (gate, up, down) — not all E: 4 x 2048^2 + 2048 x 64 + 8 x 3 x 2048 x
    1024 = 67,239,936; the untied head d V = 2048 x 50304 = 103,022,592
    (embedding look-ups are not matmuls). At one layer and s = 4096:
    6 x 170,262,528 + 12 x 4096 x 2048 / 2 = 1,071,906,816, ~1.07 GFLOP a
    token, of which the head is 57.7%, the eight active experts 28.2%, the
    attention projections 9.4%, attention itself 4.7%. Optimizer and
    recomputed operations do not count."""
    d, m, layers = (cfg["hidden_size"], cfg["intermediate_size"],
                    cfg["num_hidden_layers"])
    per_layer = (4 * d * d + d * cfg["num_experts"]
                 + cfg["num_experts_per_tok"] * 3 * d * m)
    matmul_params = layers * per_layer + d * cfg["vocab_size"]
    attention = 12 * layers * cfg["seq_len"] * d // 2
    return 6 * matmul_params + attention
