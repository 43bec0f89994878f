"""Work counts of the `nemotron` family, from the configuration's shapes.

An example is ONE POSITION of a sequence (a token), so examples a second
are tokens a second. Every count is of the share of the deployment this
chip holds (`num_hidden_layers`, `mamba_num_heads`, `n_groups`,
`num_attention_heads`, `num_key_value_heads`, `n_routed_experts` and
`vocab_size` of the configuration are what is held here) and is the LEAST
work of the mathematics: the scan as its recurrence (a state write and a
state read, `N x P` multiply-adds each, a head a token: whatever a chunked
form adds is the implementation's), of the attention scores only the
causal half, `T (T + 1) / 2` pairs a head, no recomputation (remat counts
nothing), 2 FLOPs a multiply-add, the backward twice the forward. The
lookups count zero; so do the convolution, the gates and the norms
(elementwise).

The routed experts are counted at their EXPECTED load: a token's
`num_experts_per_tok` choices fall on the held experts of the router's
`router_outputs` with probability held / outputs each, 0.34 held experts a
token at 22 x 8 / 512. The measured load is the program's counter
`moe_pairs`. The router, the latent projections and the shared expert see
every token.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

META_COLS = 3  # freq, version, dirty: int32 each


def kinds(config: Dict) -> str:
    first = config["deployment"].get("first_layer_held", 0)
    return config["hybrid_override_pattern"][
        first:first + config["num_hidden_layers"]]


def layer_counts(config: Dict) -> Tuple[int, int, int]:
    """(Mamba-2 layers, attention layers, expert layers) held here."""
    k = kinds(config)
    return k.count("M"), k.count("*"), k.count("E")


def mamba_products(config: Dict) -> List[Tuple[int, int]]:
    """(in, out) of a Mamba-2 mixer's two products a position: the input
    projection to z, x, B, C and dt of the held heads and group, and the
    output projection."""
    d, H, P = (config["hidden_size"], config["mamba_num_heads"],
               config["mamba_head_dim"])
    gn = config["n_groups"] * config["ssm_state_size"]
    return [(d, 2 * H * P + 2 * gn + H), (H * P, d)]


def attn_products(config: Dict) -> List[Tuple[int, int]]:
    d, H, Hkv, D = (config["hidden_size"], config["num_attention_heads"],
                    config["num_key_value_heads"], config["head_dim"])
    return [(d, H * D), (d, Hkv * D), (d, Hkv * D), (H * D, d)]


def latent_products(config: Dict) -> List[Tuple[int, int]]:
    d, lat = config["hidden_size"], config["moe_latent_size"]
    return [(d, lat), (lat, d)]


def expert_macs(config: Dict) -> int:
    """Multiply-adds of ONE routed expert on one token (in the latent)."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def shared_macs(config: Dict) -> int:
    return (2 * config["hidden_size"] * config["n_shared_experts"]
            * config["moe_shared_expert_intermediate_size"])


def scan_macs_per_token(config: Dict) -> int:
    """The recurrence's multiply-adds a token, every held head: the state
    write `dt B x^T` and the read `S^T C`, N x P each."""
    return (2 * config["mamba_num_heads"] * config["ssm_state_size"]
            * config["mamba_head_dim"])


def held_experts_per_token(config: Dict) -> float:
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["deployment"]["router_outputs"])


def score_pairs(mix: Dict) -> int:
    T = mix["seq_len"]
    return T * (T + 1) // 2


def attention_macs_per_token(config: Dict, mix: Dict) -> float:
    """Q K^T and P V over the causal pairs, every held query head, a
    position on average."""
    return (config["num_attention_heads"] * 2 * config["head_dim"]
            * score_pairs(mix) / mix["seq_len"])


def forward_macs_per_token(config: Dict, mix: Dict) -> Dict[str, float]:
    """Forward multiply-adds a position, by part."""
    M, A, E = layer_counts(config)
    mm = lambda ps: sum(i * o for i, o in ps)  # noqa: E731
    d = config["hidden_size"]
    return {
        "mamba_projections": M * mm(mamba_products(config)),
        "ssd_scan": M * scan_macs_per_token(config),
        "attn_projections": A * mm(attn_products(config)),
        "attn_scores": A * attention_macs_per_token(config, mix),
        "router": E * d * config["deployment"]["router_outputs"],
        "latent_projections": E * mm(latent_products(config)),
        "experts": E * held_experts_per_token(config) * expert_macs(config),
        "shared_expert": E * shared_macs(config),
        "head": d * config["vocab_size"],
    }


def flops_per_example(config: Dict, mix: Dict) -> float:
    """Forward and backward FLOPs of the whole model a POSITION (an example
    is one position): the held share, the scan as its recurrence, the
    causal half of the scores, remat not counted, lookups zero, 0.34
    expected held experts a token."""
    return 6.0 * sum(forward_macs_per_token(config, mix).values())


def dense_params(config: Dict) -> int:
    """Every element of the dense tree (the selection bias' entries among
    them: they stand in the tree and under Adam's moments like any leaf)."""
    M, A, E = layer_counts(config)
    d, H = config["hidden_size"], config["mamba_num_heads"]
    inner = H * config["mamba_head_dim"]
    xbc = inner + 2 * config["n_groups"] * config["ssm_state_size"]
    mm = lambda ps: sum(i * o for i, o in ps)  # noqa: E731
    mamba = (mm(mamba_products(config)) + (config["conv_kernel"] + 1) * xbc
             + 3 * H + inner)                 # conv, bias; dt_bias, A, D; norm
    expert_layer = ((d + 1) * config["deployment"]["router_outputs"]
                    + mm(latent_products(config)) + shared_macs(config)
                    + config["n_routed_experts"] * expert_macs(config))
    return (M * mamba + A * mm(attn_products(config)) + E * expert_layer
            + (M + A + E) * d + d + d * config["vocab_size"])


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """The least HBM traffic of the dense forward and backward in float32,
    as the benchmark's other families count it: each weight read in the
    forward, read in the backward and its gradient written (12 B); each
    product's input read in the forward and in the backward and its
    gradient written, its output written and the output's gradient read
    (4 B x (3 in + 2 out) a position; a routed expert's at its expected
    load)."""
    M, A, E = layer_counts(config)
    positions = mix["batch"] * mix["seq_len"]
    d, lat, f = (config["hidden_size"], config["moe_latent_size"],
                 config["moe_intermediate_size"])
    fs = config["n_shared_experts"] * config["moe_shared_expert_intermediate_size"]
    per_pos = lambda ps: sum(3 * i + 2 * o for i, o in ps)  # noqa: E731
    expert_layer = (per_pos([(d, config["deployment"]["router_outputs"])])
                    + per_pos(latent_products(config))
                    + held_experts_per_token(config)
                    * per_pos([(lat, f), (f, lat)])
                    + per_pos([(d, fs), (fs, d)]))
    acts = (M * per_pos(mamba_products(config))
            + A * per_pos(attn_products(config)) + E * expert_layer
            + per_pos([(d, config["vocab_size"])]))
    return 12.0 * dense_params(config) + 4.0 * positions * acts


def engine_bytes_per_unique(config: Dict) -> float:
    """Algorithmic HBM bytes of the embedding engine per unique id per
    step, as the benchmark's other families count them: key gather and
    claim scatter, the row gathered once and scattered once, Adagrad's
    accumulator gathered and scattered, the fused metadata gathered and
    scattered."""
    D = config["emb_dim"]
    value_b = {"float32": 4, "bfloat16": 2}[config["table_dtype"]]
    slot_b = {"adagrad": 4 * D}[config["sparse_optimizer"]["name"]]
    return float(2 * 4 + 2 * D * value_b + 2 * slot_b + 2 * META_COLS * 4)


def router_even_load_per_step(config: Dict, mix: Dict) -> float:
    """What an even router gives EVERY one of its outputs, summed over the
    expert layers held here, a step: positions x experts a token / outputs
    a layer (352 x 5 at the cell's sizes)."""
    _, _, E = layer_counts(config)
    return (E * mix["batch"] * mix["seq_len"]
            * config["num_experts_per_tok"]
            / config["deployment"]["router_outputs"])


# ------------------------------------------- the parts a roofline is read for


def ssd_scan_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the Mamba-2 scan, forward and backward,
    every Mamba-2 layer, a step: the recurrence's two N x P products a head
    a token. Bytes: the forward reads x, B and C (bf16; B and C at the
    GROUP, which its heads share) and dt (f32) and writes y (f32); the
    backward reads all of those and y's gradient and writes the four
    gradients."""
    M, _, _ = layer_counts(config)
    positions = mix["batch"] * mix["seq_len"]
    H, P = config["mamba_num_heads"], config["mamba_head_dim"]
    gn = config["n_groups"] * config["ssm_state_size"]
    ins = 2 * (H * P + 2 * gn) + 4 * H
    out = 4 * H * P
    per_pos = (ins + out) + (ins + out) + ins
    return (6.0 * M * positions * scan_macs_per_token(config),
            float(M * positions * per_pos))


def flash_attn_work_per_step(config: Dict, mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of causal attention, forward and backward,
    every attention layer, a step: two products forward and four backward
    (dV, dP, dQ, dK; the scores' recomputation counts nothing). Bytes: q,
    k, v, o and their gradients once each in bf16."""
    _, A, _ = layer_counts(config)
    positions = mix["batch"] * mix["seq_len"]
    H, Hkv, D = (config["num_attention_heads"],
                 config["num_key_value_heads"], config["head_dim"])
    per_pos = 2 * 2 * (2 * H * D + 2 * Hkv * D)
    return (6.0 * A * positions * attention_macs_per_token(config, mix),
            float(A * positions * per_pos))


def experts_work_per_step(config: Dict, mix: Dict,
                          pairs_per_step: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the held experts' grouped products, forward
    and backward, a step, for the MEASURED (token, expert) pairs a step
    (all expert layers). Bytes: every held expert's weights read in the
    forward and in the backward and their gradients written (12 B a
    weight), and a pair's rows: input and output (latent wide), hidden and
    its square (expert wide), each with its gradient, in bf16."""
    _, _, E = layer_counts(config)
    lat, f = config["moe_latent_size"], config["moe_intermediate_size"]
    weights = 12.0 * E * config["n_routed_experts"] * expert_macs(config)
    rows = 2 * 2 * (2 * lat + 2 * f)
    return (6.0 * pairs_per_step * expert_macs(config),
            weights + pairs_per_step * rows)
