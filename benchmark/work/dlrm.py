"""Work counts of the DLRM family (`"work": "dlrm"` in a configuration's
file): the operations and bytes a step needs, from the configuration's
widths and the mix's batch.

These count the work, not the implementation: they read the same whichever
kernel does it, and a later PR cannot change them. Checked by
tests/test_counts.py against numbers worked out by hand. The contract of a
work module (PERF.md section 3): `flops_per_example(config, mix)`,
`dense_min_bytes_per_step(config, mix)`, `engine_bytes_per_unique(config)`;
a function a family does not have is absent and its reader returns None. It
imports nothing of the program.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

META_COLS = 3  # freq, version, dirty: int32 each


def dense_layers(config: Dict) -> List[Tuple[int, int]]:
    """(in, out) of every matrix product of the dense model, in order."""
    D, fields = config["emb_dim"], config["num_cat"] + 1
    layers, d = [], config["num_dense"]
    for h in config["bottom_mlp"]:
        layers.append((d, h))
        d = h
    if config["interaction"] == "dot":
        d = fields * (fields - 1) // 2 + D
    else:
        d = fields * D
        layers += [(d, d)] * config["cross_depth"]
    for h in config["top_mlp"]:
        layers.append((d, h))
        d = h
    return layers


def flops_per_example(config: Dict, mix: Dict) -> float:
    """An example is one row of a Criteo batch. Forward and backward of the
    whole model: the MLPs, the cross layers and the pairwise
    interaction: 2 FLOPs a multiply-add, the backward twice the forward
    (one product for the inputs' gradient, one for the weights'). The
    interaction counts the F(F-1)/2 pairs the model uses, not the F x F the
    einsum computes. The lookups count zero."""
    macs = sum(i * o for i, o in dense_layers(config))
    if config["interaction"] == "dot":
        fields = config["num_cat"] + 1
        macs += fields * (fields - 1) // 2 * config["emb_dim"]
    return 6.0 * macs


def dense_min_bytes_per_step(config: Dict, mix: Dict) -> float:
    """The least HBM traffic of the dense forward and backward in float32:
    each weight read in the forward, read in the backward and its gradient
    written (12 B); each layer's input read in the forward and in the
    backward and its gradient written, its output written and the output's
    gradient read (4 B x (3 in + 2 out) an example)."""
    layers = dense_layers(config)
    weights = 12.0 * sum(i * o for i, o in layers)
    acts = 4.0 * mix["batch"] * sum(3 * i + 2 * o for i, o in layers)
    return weights + acts


def engine_bytes_per_unique(config: Dict) -> float:
    """Algorithmic HBM bytes of the embedding engine per unique id per step
    (a copy of the program's ops/traffic.py::table_step_traffic, unsharded,
    `diet=True`): key gather and claim scatter, the row gathered once and
    scattered once, the optimizer's slots gathered and scattered, the fused
    metadata gathered and scattered. The initializer's scatter of a new row
    is growth, not step traffic, and is left out."""
    D = config["emb_dim"]
    value_b = {"float32": 4, "bfloat16": 2}[config["table_dtype"]]
    slot_b = {"adagrad": 4 * D}[config["sparse_optimizer"]["name"]]
    probe = 2 * 4
    value = 2 * D * value_b
    slots = 2 * slot_b
    meta = 2 * META_COLS * 4
    return float(probe + value + slots + meta)
