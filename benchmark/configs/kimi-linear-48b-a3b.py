"""Kimi-Linear-48B-A3B-Instruct, one chip's share of a 32-chip deployment
(experts 0..7 of 256; rows 0..20,479 of the vocabulary, which 8 chips
divide), cut in depth to the leading dense layer and one whole period
after it: the program's model, its batches, its plain reference and its
operations per token. Sizes come from ``kimi-linear-48b-a3b.json``
(``cfg``).

Parameters by hand (d 2304; the file's ``n_params``). A KDA mixer: q, k, v
3 x 2304 x 4096 = 28,311,552; their convolutions 3 x 4 x 4096 = 49,152;
the two low-rank gates 2 x (2304 x 128 + 128 x 4096) = 1,638,400; the output
gate's bias 4096; ``A_log`` 32 and ``dt_bias`` 4096; beta 2304 x 32 =
73,728; the head norm 128; o 4096 x 2304 = 9,437,184: **39,518,368**. An MLA
mixer: q 2304 x 6144 = 14,155,776; kv_a 2304 x 576 = 1,327,104; its norm
512; kv_b 512 x 8192 = 4,194,304; o 9,437,184: **29,114,880**. One expert
3 x 2304 x 1024 = 7,077,888 (so is the shared one); router and selection
bias 2304 x 256 + 256 = 590,080; a layer's two norms 4,608. The dense layer
(KDA + 3 x 2304 x 9216 = 63,700,992) 103,223,968; a KDA expert layer
(39,518,368 + 4,608 + 590,080 + 9 x 7,077,888) 103,814,048; the MLA expert
layer 93,410,560; embedding + head + final norm 2 x 20,480 x 2304 + 2304 =
94,374,144. Layers 1-5 are dense-KDA, KDA, KDA, MLA, KDA: 103,223,968 + 3 x
103,814,048 + 93,410,560 + 94,374,144 = **602,450,816**. Published, 1 dense
+ 19 KDA + 7 MLA layers with all 256 experts and 163,840 rows: 49.1 B, and
3.5 B of them met by a token.
"""

import jax.numpy as jnp
import numpy as np

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``): the ``kda`` and ``eshare`` readers
# both ask, the first to ask pays. ``lib/cell.py`` hands a reader neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 32 that share a layer
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length (tracing at the cell's 8,192 tokens takes as long on the
# chip's host: 47 s of set-up either way; PERF.md section 7, PR 39).
EXAMPLE = np.zeros((1, 8), np.int32)


def _kinds(cfg):
    from byteps_tpu.models.kimi_linear import layer_kinds

    linear = cfg["linear_attn_config"]
    return layer_kinds(linear["kda_layers"], linear["full_attn_layers"],
                       cfg["num_hidden_layers"])


def _model(cfg):
    from byteps_tpu.models import KimiLinearModel

    linear = cfg["linear_attn_config"]
    return KimiLinearModel(
        vocab_size=cfg["vocab_size"], layer_kinds=_kinds(cfg),
        d_model=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        head_dim=linear["head_dim"], gate_rank=linear["head_dim"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], kv_rank=cfg["kv_lora_rank"],
        dense_mlp_dim=cfg["intermediate_size"],
        num_experts=cfg["num_experts"],
        num_local_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_token"],
        mlp_dim=cfg["moe_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        first_dense=cfg["first_k_dense_replace"],
        shared=cfg["num_shared_experts"], first_expert=FIRST_EXPERT,
        conv_kernel=linear["short_conv_kernel_size"],
        chunk=cfg["kda_chunk"], sub_chunk=cfg["kda_sub_chunk"],
        loss_rows=cfg["loss_rows"], dtype=jnp.dtype(cfg["compute_dtype"]),
        eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import kimi_linear_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return kimi_linear_loss(model.apply(params, batch["tokens"]))

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats", "kda_stats"}``, the model's two
    collections as numpy, under one jit that returns them alone (the
    compiler drops the head). Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, EXAMPLE), tokens,
                               mutable=["moe_stats", "kda_stats"])[1]

        STATS.update(jax.tree_util.tree_map(np.asarray, dict(stats(
            jax.random.PRNGKey(FIRST["seed"]), FIRST["tokens"][:rows]))))
    return STATS


def make_batch(cfg, rng, rows):
    """One global batch of ``rows`` sequences, one document each: uniform
    tokens over this chip's slice of the vocabulary."""
    batch = {"tokens": rng.integers(0, cfg["vocab_size"],
                                    (rows, cfg["seq_len"]), dtype=np.int32)}
    if not FIRST:
        FIRST.update(seed=rng.bit_generator.seed_seq.entropy,
                     tokens=batch["tokens"])
    return batch


def reference_weights(cfg, batch, shards):
    """[rows, s-1], summing to 1: the loss is the mean over rows x (s-1)
    positions and nothing else, so the shards need not be told apart."""
    rows, seq = batch["tokens"].shape
    return np.full((rows, seq - 1), 1.0 / (rows * (seq - 1)), np.float32)


def reference_loss(cfg):
    from benchmark.lib.plain_kimi_linear import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(
            params, batch["tokens"], heads=cfg["num_attention_heads"],
            kv_rank=cfg["kv_lora_rank"], v_dim=cfg["v_head_dim"],
            top_k=cfg["num_experts_per_token"], first_expert=FIRST_EXPERT,
            routed_scale=cfg["routed_scaling_factor"],
            eps=cfg["rms_norm_eps"], dtype=jnp.dtype(cfg["compute_dtype"]),
            **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the recurrence token by token, causal pairs, the expected
    held experts, the sliced head — so that a chunk's extra products, the
    padded lanes of a 192-wide head, rows beyond the held groups and
    recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). A KDA mixer's projections (the parameters above less
    convolutions, biases, ``A_log``, ``dt_bias`` and the norm): 28,311,552 +
    1,638,400 + 73,728 + 9,437,184 = 39,460,864; its recurrence, per head
    and token 7 x 128 x 128 (decay 1, S^T k 2, the rank-one update 2, S^T q
    2) = 114,688, x 32 heads x 3 (forward, and twice that backward) =
    11,010,048. An MLA mixer's projections 29,114,368; its attention 6 x
    (192 + 128) x 32 = 61,440 a causal (query, key) pair, (s + 1) / 2 pairs
    a query: 251,688,960 at s 8,192 (503,347,200 at 16,384). The dense
    SwiGLU 63,700,992. An expert layer: router 589,824, of a token's 8
    experts the 8 x 8 / 256 = 1/4 expected here, 1,769,472, and the shared
    expert 7,077,888: 9,437,184. The head 2304 x 20,480 = 47,185,920
    (embedding look-ups are not matmuls). Layers 1-5: 6 x (4 x 39,460,864 +
    29,114,368 + 63,700,992 + 4 x 9,437,184 + 47,185,920) + 4 x 11,010,048
    = 2,013,560,832 + 44,040,192 = 2,057,601,024 and the attention: at s
    8,192 **2,309,289,984** (2.31 GFLOP a token: 11% the MLA layer's score
    and value products, 41% the KDA layers' projections, 1.9% their
    recurrence), at s 16,384 2,560,948,224. (ISSUE 39 counts 2,517,326,784
    at 16,384; without the recurrence this count is 2,516,908,032 there,
    and the 418,752 between them I could not place.)"""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    heads, linear = cfg["num_attention_heads"], cfg["linear_attn_config"]
    kinds = _kinds(cfg)
    width = linear["num_heads"] * linear["head_dim"]
    rank = linear["head_dim"]
    kda = (4 * d * width + 2 * (d * rank + rank * width)
           + d * linear["num_heads"])
    recurrence = 3 * 7 * linear["num_heads"] * linear["head_dim"] ** 2
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    mla = (d * heads * qk + d * (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
           + cfg["kv_lora_rank"] * heads * (cfg["qk_nope_head_dim"]
                                            + cfg["v_head_dim"])
           + heads * cfg["v_head_dim"] * d)
    attention = 6 * (qk + cfg["v_head_dim"]) * heads * (s + 1) // 2
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = (d * cfg["num_experts"] + cfg["num_shared_experts"] * expert
           + cfg["num_experts_per_token"] * cfg["num_local_experts"] * expert
           // cfg["num_experts"])
    dense = cfg["first_k_dense_replace"]
    total = 6 * d * cfg["vocab_size"]
    for i, kind in enumerate(kinds):
        ffn = 3 * d * cfg["intermediate_size"] if i < dense else moe
        total += 6 * ffn + (6 * kda + recurrence if kind == "kda"
                            else 6 * mla + attention)
    return total
