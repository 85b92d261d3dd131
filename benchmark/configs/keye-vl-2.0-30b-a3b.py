"""Keye-VL-2.0-30B-A3B's language model, one chip's share of an eight-chip
deployment (experts 0..15 of 128, rows 0..18,991 of the vocabulary), cut in
depth: the program's model, its batches, its plain reference and its
operations per token. Sizes come from ``keye-vl-2.0-30b-a3b.json``
(``cfg``)."""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``): the ``dsa`` and ``eshare`` readers
# both ask, the first to ask pays. ``lib/cell.py`` hands a reader neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the eight that share a layer


def _model(cfg):
    from byteps_tpu.models import KeyeModel

    sa = cfg["sa_config"]
    return KeyeModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        num_experts=cfg["num_experts"],
        num_local_experts=cfg["num_local_experts"],
        first_expert=FIRST_EXPERT, top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["moe_intermediate_size"],
        index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
        index_topk=sa["topk"], block=sa["q_chunk_size"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        remat_experts=cfg["recompute_experts"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import keye_loss

    model = _model(cfg)
    example = np.zeros((1, cfg["seq_len"]), np.int32)

    def init(key):
        return model.init(key, example)

    def loss_fn(params, batch):
        return keye_loss(model.apply(params, batch["tokens"]),
                         batch["tokens"],
                         load_balance_weight=cfg["load_balance_weight"],
                         index_loss_weight=cfg["index_loss_weight"])

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats", "dsa_stats"}``, the model's two
    collections as numpy, under one jit that returns them alone (the
    compiler drops the head). Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)
        example = np.zeros((1, cfg["seq_len"]), np.int32)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, example), tokens,
                               mutable=["moe_stats", "dsa_stats"])[1]

        STATS.update(jax.tree_util.tree_map(np.asarray, dict(stats(
            jax.random.PRNGKey(FIRST["seed"]), FIRST["tokens"][:rows]))))
    return STATS


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences: uniform tokens over this
    chip's slice of the vocabulary."""
    batch = {"tokens": rng.integers(0, cfg["vocab_size"],
                                    (rows, cfg["seq_len"]), dtype=np.int32)}
    if not FIRST:
        FIRST.update(seed=rng.bit_generator.seed_seq.entropy,
                     tokens=batch["tokens"])
    return batch


def reference_weights(cfg, batch, shards):
    """[shards, rows per shard, s-1], summing to 1: the cross-entropy is the
    mean over rows x (s-1) positions, and every shard has as many. The
    leading axis says which rows a chip holds: the load-balancing loss and
    the indexer's loss are statistics of one chip's tokens, so the
    reference works them out per shard and averages."""
    rows, seq = batch["tokens"].shape
    return np.full((shards, rows // shards, seq - 1),
                   1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    from benchmark.lib.plain_keye import causal_lm_nll_and_aux

    sa = cfg["sa_config"]

    def weighted_loss(params, batch):
        weight = batch["weight"]
        shards, rows = weight.shape[:2]
        tokens = batch["tokens"].reshape(shards, rows, -1)
        total = 0.0
        for i in range(shards):
            nll, load_balance, index_loss = causal_lm_nll_and_aux(
                params, tokens[i], num_layers=cfg["num_hidden_layers"],
                num_heads=cfg["num_attention_heads"],
                num_kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], top_k=cfg["num_experts_per_tok"],
                first_expert=FIRST_EXPERT,
                index_heads=sa["indexer_num_heads"], index_topk=sa["topk"],
                block=sa["q_chunk_size"], eps=cfg["rms_norm_eps"],
                rope_theta=float(cfg["rope_theta"]),
                dtype=jnp.dtype(cfg["compute_dtype"]))
            total = total + (nll * weight[i]).sum() + (
                cfg["load_balance_weight"] * load_balance
                + cfg["index_loss_weight"] * index_loss) / shards
        return total

    return weighted_loss


def attended_pairs(seq_len, topk):
    """(selected, causal) (query, key) pairs of one sequence: query t
    attends min(t + 1, topk) keys of its t + 1 causal ones."""
    causal = seq_len * (seq_len + 1) // 2
    if seq_len <= topk:
        return causal, causal
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk, causal


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — selected keys, causal index scores, the expected held
    experts, the sliced head — so that masked-out products, rows beyond the
    held groups and recomputation earn no MFU.

    Per layer a token meets, at 6 operations a matmul parameter (forward,
    input gradient, weight gradient): Q, K, V, O = 2048 x 4096 + 2 x 2048 x
    512 + 4096 x 2048 = 18,874,368; the router 2048 x 128 = 262,144; of its
    8 experts the 8 x 16 / 128 = 1 expected here, 3 x 2048 x 768 =
    4,718,592. The indexer's projections, 2048 x (1024 + 64 + 16) =
    2,260,992, at 4 a parameter: their input is detached, so there is no
    input gradient. Attention over the selected keys: 12 x 32 x 128 a
    (query, key) pair (QK and PV, forward and both gradients), over
    14,681,088 pairs a sequence of 8192 = 1792.125 a query. Index scores
    over the causal pairs, 33,558,528 = 4096.5 a query: 6 x 16 x 64 a pair.
    Per layer 6 x 23,855,104 + 4 x 2,260,992 + (49,152 x 14,681,088 + 6,144
    x 33,558,528) // 8192 = 143,130,624 + 9,043,968 + 113,255,424 =
    265,430,016; the head 6 x 2048 x 18,992 = 233,373,696 (embedding
    look-ups are not matmuls). At 4 layers 1,295,093,760, at 6 layers
    1,825,953,792: ~1.3 and ~1.8 GFLOP a token, of which at 6 layers
    indexer and selected attention are 40%, the attention projections 37%,
    the held experts and the router 10%, the head 13%."""
    d, m, layers, s = (cfg["hidden_size"], cfg["moe_intermediate_size"],
                       cfg["num_hidden_layers"], cfg["seq_len"])
    heads, kv_heads, head_dim = (cfg["num_attention_heads"],
                                 cfg["num_key_value_heads"], cfg["head_dim"])
    sa = cfg["sa_config"]
    index_width = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    projections = 2 * d * head_dim * (heads + kv_heads)
    held = (cfg["num_experts_per_tok"] * cfg["num_local_experts"] * 3 * d * m
            // cfg["num_experts"])
    indexer = d * (index_width + sa["indexer_head_dim"]
                   + sa["indexer_num_heads"])
    selected, causal = attended_pairs(s, sa["topk"])
    attention = (12 * heads * head_dim * selected
                 + 6 * index_width * causal) // s
    per_layer = (6 * (projections + d * cfg["num_experts"] + held)
                 + 4 * indexer + attention)
    return layers * per_layer + 6 * d * cfg["vocab_size"]
