"""Laguna-XS.2, one chip's share of a 16-chip deployment (experts 0..15 of
256; rows 0..12,543 of the vocabulary, which 8 chips divide), cut in depth
to published layers 0-4 — global attention with the dense SwiGLU, three
windowed layers and a second global one with expert layers: the program's
model, its batches, its plain reference and its operations per token.
Sizes come from ``laguna-xs.2.json`` (``cfg``).

Parameters by hand (d 2048, head 128, 8 key heads; the file's
``n_params``). Attention of a global layer (48 heads): q 2048 x 6144 =
12,582,912; k and v 2 x 2048 x 1024 = 4,194,304; o 6144 x 2048 =
12,582,912; gate 2048 x 48 = 98,304: **29,458,432**. Of a windowed layer
(64 heads): 16,777,216 + 4,194,304 + 16,777,216 + 131,072 = **37,879,808**.
One expert 3 x 2048 x 512 = 3,145,728 (so is the shared one); router 2048 x
256 = 524,288; a layer's two norms 4,096; the dense SwiGLU 3 x 2048 x 8192 =
50,331,648. Layer 0 29,458,432 + 4,096 + 50,331,648 = 79,794,176; a windowed
expert layer held here 37,879,808 + 4,096 + 524,288 + 17 x 3,145,728 =
91,885,568; the global one 83,464,192; embedding + head + final norm 2 x
12,544 x 2048 + 2048 = 51,382,272. 79,794,176 + 3 x 91,885,568 + 83,464,192
+ 51,382,272 = **490,297,344**. Published, 40 layers (10 global, 30
windowed, 1 dense and 39 with all 256 experts + the shared one,
808,976,384 of experts and router a layer) and 100,352 rows: 10 x
29,458,432 + 30 x 37,879,808 + 40 x 4,096 + 50,331,648 + 39 x 808,976,384 +
2 x 100,352 x 2048 + 2048 = **33,442,596,864** (the card's 33.4 B).
"""

import jax.numpy as jnp
import numpy as np

# pairs of one head over one sequence: the causal triangle s (s + 1) / 2, or
# under a window sum_q min(q + 1, window) = 512 s - 130,816; the roofline's
# reader keeps the function
from benchmark.layers.swa import needed_pairs

# The run's seed and first batch, as ``make_batch`` saw them, and what the
# probe made of them (``layer_stats``). ``lib/cell.py`` hands a reader
# neither.
FIRST = {}
STATS = {}

FIRST_EXPERT = 0       # this chip is rank 0 of the 16 that share a layer
# What ``init`` traces the model with: no parameter's shape turns on the
# sequence length.
EXAMPLE = np.zeros((1, 8), np.int32)


def _lists(cfg):
    """The first ``num_hidden_layers`` entries of the three per-layer
    lists: kinds, query heads, feed-forwards."""
    n = cfg["num_hidden_layers"]
    return (tuple(cfg["layer_types"][:n]),
            tuple(cfg["num_attention_heads_per_layer"][:n]),
            tuple(cfg["mlp_layer_types"][:n]))


def _rotary(group):
    from byteps_tpu.models.laguna import Rotary

    yarn = None
    if group["rope_type"] == "yarn":
        yarn = (float(group["factor"]),
                group["original_max_position_embeddings"],
                float(group["beta_fast"]), float(group["beta_slow"]),
                group["attention_factor"])
    return Rotary(float(group["rope_theta"]),
                  group["partial_rotary_factor"], yarn)


def _model(cfg):
    from byteps_tpu.models import LagunaModel

    if not cfg["gating"] or cfg["moe_apply_router_weight_on_input"]:
        raise ValueError("LagunaModel gates every head's output and weighs "
                         "the experts' outputs")
    kinds, heads, ffn = _lists(cfg)
    rope = cfg["rope_parameters"]
    return LagunaModel(
        vocab_size=cfg["vocab_size"], layer_kinds=kinds, layer_heads=heads,
        layer_ffn=ffn, d_model=cfg["hidden_size"],
        kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        window=cfg["sliding_window"],
        full_rotary=_rotary(rope["full_attention"]),
        window_rotary=_rotary(rope["sliding_attention"]),
        dense_mlp_dim=cfg["intermediate_size"],
        num_experts=cfg["num_experts"],
        num_local_experts=cfg["num_local_experts"],
        top_k=cfg["num_experts_per_tok"],
        mlp_dim=cfg["moe_intermediate_size"],
        routed_scale=cfg["moe_routed_scaling_factor"],
        shared_mlp_dim=cfg["shared_expert_intermediate_size"],
        first_expert=FIRST_EXPERT, loss_rows=cfg["loss_rows"],
        dtype=jnp.dtype(cfg["compute_dtype"]), eps=cfg["rms_norm_eps"])


def build(cfg):
    """The system's own model: ``(init(key) -> params, loss_fn(params,
    batch) -> scalar)`` as a user of byteps_tpu writes them."""
    from byteps_tpu.models import laguna_loss

    model = _model(cfg)

    def init(key):
        return model.init(key, EXAMPLE)

    def loss_fn(params, batch):
        return laguna_loss(model.apply(params, batch["tokens"]))

    return init, loss_fn


def layer_stats(cfg, rows):
    """What the run's first ``rows`` sequences do in the model with the
    run's own weights: ``{"moe_stats"}``, the model's collection as numpy,
    under one jit that returns it alone (the compiler drops the head).
    Worked out once a process."""
    if not STATS and FIRST:
        import jax

        model = _model(cfg)

        @jax.jit
        def stats(key, tokens):
            return model.apply(model.init(key, EXAMPLE), tokens,
                               mutable=["moe_stats"])[1]

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
    from benchmark.lib.plain_laguna import causal_lm_nll

    def weighted_loss(params, batch):
        nll = causal_lm_nll(
            params, batch["tokens"], layer_types=_lists(cfg)[0],
            head_dim=cfg["head_dim"], window=cfg["sliding_window"],
            rope_parameters=cfg["rope_parameters"],
            top_k=cfg["num_experts_per_tok"], first_expert=FIRST_EXPERT,
            routed_scale=cfg["moe_routed_scaling_factor"],
            eps=cfg["rms_norm_eps"], dtype=jnp.dtype(cfg["compute_dtype"]),
            **cfg["reference_blocks"])
        return (nll * batch["weight"]).sum()

    return weighted_loss


def flops_per_token(cfg):
    """Operations the mathematics needs per trained token, forward and
    backward — the pairs of the band and of the causal triangle and no
    others, the expected held experts, the sliced head at the rows with a
    target — so that the blocks a kernel walks beyond the band, rows beyond
    the held groups and recomputation earn no MFU.

    At 6 operations a matmul parameter (forward, input gradient, weight
    gradient). Attention's projections and gate are the parameters above:
    29,458,432 global, 37,879,808 windowed. A (query, key) pair of one head
    costs 2 x 128 (its score) + 2 x 128 (its value) forward and twice that
    backward, 1,536: 73,728 a pair over a global layer's 48 heads, 98,304
    over a windowed layer's 64. A sequence of 8,192 has 33,558,528 causal
    pairs and 512 x 8,192 - 130,816 = 4,063,488 in the band: two global
    layers 4.948 TFLOP, three windowed 1.198. The dense SwiGLU 50,331,648.
    An expert layer: router 524,288, of a token's 8 experts the 8 x 16 /
    256 = 1/2 expected here, 1,572,864, and the shared expert 3,145,728:
    5,242,880. The head 2048 x 12,544 = 25,690,112 at the s - 1 rows with a
    target (embedding look-ups are not matmuls). A row of the stack 6 x (2
    x 29,458,432 + 3 x 37,879,808 + 50,331,648 + 4 x 5,242,880) =
    1,463,156,736; over s 8,192: 1,463,156,736 + 750,339,072 (the pairs) +
    154,121,856 (the head) = **2,367,617,664** a token, 19.40 TFLOP a step
    (32% the score and value products, 44% the five layers' projections
    and gates). ISSUE 47 counts 789 M forward a token, 2,367 M with the
    backward pass: the same to its three digits."""
    d, s = cfg["hidden_size"], cfg["seq_len"]
    head_dim, kv = cfg["head_dim"], cfg["num_key_value_heads"]
    expert = 3 * d * cfg["moe_intermediate_size"]
    moe = (d * cfg["num_experts"]
           + 3 * d * cfg["shared_expert_intermediate_size"]
           + cfg["num_experts_per_tok"] * cfg["num_local_experts"] * expert
           // cfg["num_experts"])
    row, pairs = 0, 0
    for kind, heads, ffn in zip(*_lists(cfg)):
        row += (2 * d * heads * head_dim + 2 * d * kv * head_dim + d * heads
                + (3 * d * cfg["intermediate_size"] if ffn == "dense"
                   else moe))
        pairs += 6 * 2 * head_dim * heads * needed_pairs(
            s, cfg["sliding_window"] if kind == "sliding_attention"
            else None)
    return (s * 6 * row + pairs + (s - 1) * 6 * d * cfg["vocab_size"]) // s
