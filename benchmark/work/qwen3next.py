"""Work counts of the `qwen3next` family, from the configuration's shapes.

An example is ONE POSITION of a sequence (a token), so examples a second
are tokens a second. Every count is of the share of the deployment this
chip holds (`num_hidden_layers`, `num_experts` and `vocab_size` of the
configuration are what is held here) and is the LEAST work of the
mathematics: the causal half of the attention scores counted once, the
delta rule's three `d_k x d_v` products a head a token, no recomputation
(remat and the chunked form's extra products count nothing), 2 FLOPs a
multiply-add, the backward twice the forward. The lookups count zero.

The routed experts are counted at their EXPECTED load: a token's
`num_experts_per_tok` choices fall on the `num_experts` held of the
router's `router_outputs` with probability held / outputs each, 0.625
held experts a token at 10 x 32 / 512. The measured load is the program's
counter `moe_pairs` (`experts_flops_per_pair` x pairs is the kernels' own
count).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

META_COLS = 3  # freq, version, dirty: int32 each


def is_attention(config: Dict, i: int) -> bool:
    return (i + 1) % config["full_attention_interval"] == 0


def _layers(config: Dict) -> Tuple[int, int]:
    L = config["num_hidden_layers"]
    attn = sum(is_attention(config, i) for i in range(L))
    return L - attn, attn


def gdn_products(config: Dict) -> List[Tuple[int, int]]:
    """(in, out) of a gated-delta layer's mixer products, a position."""
    d = config["hidden_size"]
    key = config["linear_num_key_heads"] * config["linear_key_head_dim"]
    value = config["linear_num_value_heads"] * config["linear_value_head_dim"]
    return [(d, 2 * key + 2 * value),
            (d, 2 * config["linear_num_value_heads"]), (value, d)]


def attn_products(config: Dict) -> List[Tuple[int, int]]:
    d, D = config["hidden_size"], config["head_dim"]
    H, Hkv = config["num_attention_heads"], config["num_key_value_heads"]
    return [(d, 2 * H * D), (d, Hkv * D), (d, Hkv * D), (H * D, d)]


def expert_macs(config: Dict) -> int:
    """Multiply-adds of ONE routed expert on one token."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def held_experts_per_token(config: Dict) -> float:
    return (config["num_experts_per_tok"] * config["num_experts"]
            / config["deployment"]["router_outputs"])


def moe_dense_products(config: Dict) -> List[Tuple[int, int]]:
    """The expert block's products every token takes: router, the shared
    expert and its gate."""
    d, fs = config["hidden_size"], config["shared_expert_intermediate_size"]
    return [(d, config["deployment"]["router_outputs"]), (d, fs), (d, fs),
            (fs, d), (d, 1)]


def rule_macs_per_token(config: Dict) -> int:
    """The delta rule as the recurrence writes it: S^T k, k delta^T and
    S^T q, each d_k x d_v a value head."""
    return (3 * config["linear_num_value_heads"]
            * config["linear_key_head_dim"] * config["linear_value_head_dim"])


def attention_macs_per_token(config: Dict, mix: Dict) -> float:
    """Q K^T and P V over the causal half: (L + 1) / 2 keys a query on
    average, every query head."""
    keys = (mix["seq_len"] + 1) / 2.0
    return 2.0 * config["num_attention_heads"] * config["head_dim"] * keys


def forward_macs_per_token(config: Dict, mix: Dict) -> Dict[str, float]:
    """Forward multiply-adds a position, by part."""
    gdn, attn = _layers(config)
    conv = (config["linear_conv_kernel_dim"]
            * (2 * config["linear_num_key_heads"]
               * config["linear_key_head_dim"]
               + config["linear_num_value_heads"]
               * config["linear_value_head_dim"]))
    moe = (sum(i * o for i, o in moe_dense_products(config))
           + held_experts_per_token(config) * expert_macs(config))
    return {
        "gdn_projections": gdn * (sum(i * o for i, o in gdn_products(config))
                                  + conv),
        "gdn_rule": gdn * rule_macs_per_token(config),
        "attn_projections": attn * sum(i * o
                                       for i, o in attn_products(config)),
        "attn_scores": attn * attention_macs_per_token(config, mix),
        "moe": (gdn + attn) * moe,
        "head": config["hidden_size"] * config["vocab_size"],
    }


def flops_per_example(config: Dict, mix: Dict) -> float:
    """Forward and backward FLOPs of the whole model a POSITION (an example
    is one position): the held share, the causal half counted once, remat
    not counted, lookups zero, 0.625 expected held experts a token."""
    return 6.0 * sum(forward_macs_per_token(config, mix).values())


def dense_params(config: Dict) -> int:
    gdn, attn = _layers(config)
    d = config["hidden_size"]
    conv = (config["linear_conv_kernel_dim"]
            * (2 * config["linear_num_key_heads"]
               * config["linear_key_head_dim"]
               + config["linear_num_value_heads"]
               * config["linear_value_head_dim"]))
    gdn_small = conv + 2 * config["linear_num_value_heads"] \
        + config["linear_value_head_dim"]
    moe = (sum(i * o for i, o in moe_dense_products(config))
           + config["num_experts"] * expert_macs(config))
    per_gdn = sum(i * o for i, o in gdn_products(config)) + gdn_small
    per_attn = sum(i * o for i, o in attn_products(config)) \
        + 2 * config["head_dim"]
    return (gdn * per_gdn + attn * per_attn + (gdn + attn) * (moe + 2 * d)
            + d + d * config["vocab_size"])


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """The least HBM traffic of the dense forward and backward in float32,
    as the benchmark's other family counts it: each weight read in the
    forward, read in the backward and its gradient written (12 B); each
    product's input read in the forward and in the backward and its
    gradient written, its output written and the output's gradient read
    (4 B x (3 in + 2 out) a position; a routed expert's at its expected
    load)."""
    gdn, attn = _layers(config)
    positions = mix["batch"] * mix["seq_len"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    per_pos = lambda ps: sum(3 * i + 2 * o for i, o in ps)  # noqa: E731
    expert = held_experts_per_token(config) * per_pos(
        [(d, f), (d, f), (f, d)])
    acts = (gdn * per_pos(gdn_products(config))
            + attn * per_pos(attn_products(config))
            + (gdn + attn) * (per_pos(moe_dense_products(config)) + expert)
            + per_pos([(d, config["vocab_size"])]))
    return 12.0 * dense_params(config) + 4.0 * positions * acts


def engine_bytes_per_unique(config: Dict) -> float:
    """Algorithmic HBM bytes of the embedding engine per unique id per
    step, as the benchmark's other family counts them: key gather and claim
    scatter, the row gathered once and scattered once, Adagrad's
    accumulator gathered and scattered, the fused metadata gathered and
    scattered."""
    D = config["emb_dim"]
    value_b = {"float32": 4, "bfloat16": 2}[config["table_dtype"]]
    slot_b = {"adagrad": 4 * D}[config["sparse_optimizer"]["name"]]
    return float(2 * 4 + 2 * D * value_b + 2 * slot_b + 2 * META_COLS * 4)


# ------------------------------------------- the parts a roofline is read for


def gdn_rule_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the delta rule, forward and backward, every
    gated-delta layer, a step. Bytes: the forward reads q and k (bf16, at
    the KEY heads: the repeat to the value heads moves nothing), v (bf16),
    g and beta (f32) and writes o (f32); the backward reads all of those
    and o's gradient and writes the five gradients."""
    gdn, _ = _layers(config)
    positions = mix["batch"] * mix["seq_len"]
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    qkv = 2 * (2 * Hk * dk + Hv * dv)
    gates, out = 4 * 2 * Hv, 4 * Hv * dv
    per_pos = (qkv + gates + out) + (qkv + gates + out) + (qkv + gates)
    return (6.0 * gdn * positions * rule_macs_per_token(config),
            float(gdn * positions * per_pos))


def flash_attn_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of causal attention, forward and backward,
    every attention layer, a step: two products forward and four backward
    (dV, dP, dQ, dK; the scores' recomputation counts nothing). Bytes: q,
    k, v, o and their gradients once each in bf16."""
    _, attn = _layers(config)
    positions = mix["batch"] * mix["seq_len"]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    per_pos = 2 * 2 * (2 * H * D + 2 * Hkv * D)
    return (6.0 * attn * positions * attention_macs_per_token(config, mix),
            float(attn * positions * per_pos))


def experts_work_per_step(config: Dict, mix: Dict,
                          pairs_per_step: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the held experts' grouped products, forward
    and backward, a step, for the MEASURED (token, expert) pairs a step
    (all layers). Bytes: every held expert's weights read in the forward
    and in the backward and their gradients written (12 B a weight: at a
    sixteenth of the deployed load this is what binds), and a pair's rows:
    input, the two hidden rows and the output, each with its gradient, in
    bf16."""
    layers = config["num_hidden_layers"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    weights = 12.0 * layers * config["num_experts"] * expert_macs(config)
    rows = 2 * 2 * (2 * d + 3 * f)
    return (6.0 * pairs_per_step * expert_macs(config),
            weights + pairs_per_step * rows)
