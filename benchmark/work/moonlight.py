"""Work counts of the `moonlight` family, from the configuration's shapes.

An example is ONE POSITION of a sequence (a token), so examples a second
are tokens a second. Every count is of the share of the deployment this
chip holds (`num_hidden_layers`, `n_routed_experts` and `vocab_size` of the
configuration are what is held here) and is the LEAST work of the
mathematics: of the attention scores only the causal half, `T (T + 1) / 2`
pairs a head, at the published widths (a query and a key 192 wide, a value
128: whatever width a kernel pads them to), no recomputation (remat counts
nothing), 2 FLOPs a multiply-add, the backward twice the forward. The
lookups count zero.

The routed experts are counted at their EXPECTED load: a token's
`num_experts_per_tok` choices fall on the held experts of the router's
`router_outputs` with probability held / outputs each, 0.75 held experts a
token at 6 x 8 / 64. The measured load is the program's counter
`moe_pairs`. The shared experts and a leading layer's dense feed-forward
see every token.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

META_COLS = 3  # freq, version, dirty: int32 each


def layer_kinds(config: Dict) -> Tuple[int, int]:
    """(dense layers, expert layers) held here."""
    first = config["deployment"].get("first_layer_held", 0)
    L = config["num_hidden_layers"]
    dense = min(L, max(0, config["first_k_dense_replace"] - first))
    return dense, L - dense


def attn_products(config: Dict) -> List[Tuple[int, int]]:
    """(in, out) of a layer's mixer products, a position: the queries, the
    joint down-projection to the latent and the rotary key, the
    up-projection to every head's key and value, the output."""
    d, H, r = (config["hidden_size"], config["num_attention_heads"],
               config["kv_lora_rank"])
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    return [(d, H * (dn + dr)), (d, r + dr), (r, H * (dn + dv)), (H * dv, d)]


def expert_macs(config: Dict) -> int:
    """Multiply-adds of ONE routed expert on one token."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def shared_macs(config: Dict) -> int:
    """Multiply-adds of the shared experts (fused into one) on one token."""
    return config["n_shared_experts"] * expert_macs(config)


def mlp_macs(config: Dict) -> int:
    """Multiply-adds of a dense layer's feed-forward on one token."""
    return 3 * config["hidden_size"] * config["intermediate_size"]


def held_experts_per_token(config: Dict) -> float:
    return (config["num_experts_per_tok"] * config["n_routed_experts"]
            / config["deployment"]["router_outputs"])


def score_pairs(mix: Dict) -> int:
    """(query, key) pairs of a sequence under the causal mask."""
    T = mix["seq_len"]
    return T * (T + 1) // 2


def attention_macs_per_token(config: Dict, mix: Dict) -> float:
    """Q K^T at a query's and key's width (192) and P V at a value's (128)
    over the causal pairs, every head, a position on average."""
    width = (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
             + config["v_head_dim"])
    return (config["num_attention_heads"] * width * score_pairs(mix)
            / mix["seq_len"])


def forward_macs_per_token(config: Dict, mix: Dict) -> Dict[str, float]:
    """Forward multiply-adds a position, by part."""
    dense, moe = layer_kinds(config)
    L, d = dense + moe, config["hidden_size"]
    return {
        "attn_projections": L * sum(i * o for i, o in attn_products(config)),
        "attn_scores": L * attention_macs_per_token(config, mix),
        "mlp": dense * mlp_macs(config),
        "router": moe * d * config["deployment"]["router_outputs"],
        "experts": moe * held_experts_per_token(config) * expert_macs(config),
        "shared_experts": moe * shared_macs(config),
        "head": d * config["vocab_size"],
    }


def flops_per_example(config: Dict, mix: Dict) -> float:
    """Forward and backward FLOPs of the whole model a POSITION (an example
    is one position): the held share, the causal half of the scores counted
    once at the published widths, remat not counted, lookups zero, 0.75
    expected held experts a token."""
    return 6.0 * sum(forward_macs_per_token(config, mix).values())


def dense_params(config: Dict) -> int:
    """Every element of the dense tree: what a gradient moves and, an
    expert layer, the `router_outputs` entries of the selection bias that a
    rule moves (they stand in the tree and under Adam's moments like any
    leaf)."""
    dense, moe = layer_kinds(config)
    d = config["hidden_size"]
    mixer = sum(i * o for i, o in attn_products(config)) \
        + config["kv_lora_rank"] + 2 * d           # the three norms
    expert_layer = ((d + 1) * config["deployment"]["router_outputs"]
                    + shared_macs(config)
                    + config["n_routed_experts"] * expert_macs(config))
    return ((dense + moe) * mixer + dense * mlp_macs(config)
            + moe * expert_layer + d + d * config["vocab_size"])


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """The least HBM traffic of the dense forward and backward in float32,
    as the benchmark's other families count it: each weight read in the
    forward, read in the backward and its gradient written (12 B); each
    product's input read in the forward and in the backward and its
    gradient written, its output written and the output's gradient read
    (4 B x (3 in + 2 out) a position; a routed expert's at its expected
    load)."""
    dense, moe = layer_kinds(config)
    positions = mix["batch"] * mix["seq_len"]
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    fd, fs = config["intermediate_size"], config["n_shared_experts"] * f
    per_pos = lambda ps: sum(3 * i + 2 * o for i, o in ps)  # noqa: E731
    ffn = lambda w: per_pos([(d, w), (d, w), (w, d)])  # noqa: E731
    expert_layer = (per_pos([(d, config["deployment"]["router_outputs"])])
                    + held_experts_per_token(config) * ffn(f) + ffn(fs))
    acts = ((dense + moe) * per_pos(attn_products(config)) + dense * ffn(fd)
            + moe * expert_layer + per_pos([(d, config["vocab_size"])]))
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
    a layer (768 x 5 at the cell's sizes)."""
    _, moe = layer_kinds(config)
    return (moe * mix["batch"] * mix["seq_len"]
            * config["num_experts_per_tok"]
            / config["deployment"]["router_outputs"])


# ------------------------------------------- the parts a roofline is read for


def latent_attn_work_per_step(config: Dict,
                              mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of causal latent attention, forward and
    backward, every layer, a step: two products forward and four backward
    (dV, dP, dQ, dK; the scores' recomputation counts nothing) over the
    causal pairs, the score side at a key's width (192) and the value side
    at a value's (128). Bytes: q, the per-head keys, the ONE shared rotary
    key, v, o and their gradients once each in bf16."""
    L = config["num_hidden_layers"]
    positions = mix["batch"] * mix["seq_len"]
    H = config["num_attention_heads"]
    dn, dr, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                  config["v_head_dim"])
    per_pos = 2 * 2 * (H * (dn + dr) + H * dn + dr + 2 * H * dv)
    return (6.0 * L * positions * attention_macs_per_token(config, mix),
            float(L * positions * per_pos))


# what the three flash kernels do between them is all of it: every
# attention layer held here is a latent one
flash_attn_work_per_step = latent_attn_work_per_step


def experts_work_per_step(config: Dict, mix: Dict,
                          pairs_per_step: float) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the held experts' grouped products, forward
    and backward, a step, for the MEASURED (token, expert) pairs a step
    (all expert layers). Bytes: every held expert's weights read in the
    forward and in the backward and their gradients written (12 B a
    weight), and a pair's rows: input, the two hidden rows and the output,
    each with its gradient, in bf16."""
    _, moe = layer_kinds(config)
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    weights = 12.0 * moe * config["n_routed_experts"] * expert_macs(config)
    rows = 2 * 2 * (2 * d + 3 * f)
    return (6.0 * pairs_per_step * expert_macs(config),
            weights + pairs_per_step * rows)


def shared_experts_work_per_step(config: Dict,
                                 mix: Dict) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the shared experts, forward and backward,
    every expert layer, a step: every token, no routing. Bytes: the weights
    12 B each, and a position's input, two hidden rows and output, each
    with its gradient, in bf16."""
    _, moe = layer_kinds(config)
    positions = mix["batch"] * mix["seq_len"]
    d = config["hidden_size"]
    fs = config["n_shared_experts"] * config["moe_intermediate_size"]
    rows = 2 * 2 * (2 * d + 3 * fs)
    return (6.0 * moe * positions * shared_macs(config),
            moe * (12.0 * shared_macs(config) + positions * rows))
