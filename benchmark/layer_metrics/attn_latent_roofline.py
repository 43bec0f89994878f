"""Least time of causal latent attention (two products forward and four backward over the causal pairs, the score side at a key's 192 dims and the value side at a value's 128, or its least bytes: the family's work module, `latent_attn_work_per_step`) over the device time under the scope `attn_latent`."""
from benchmark.layer_metrics import _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
READS = {"scope": "attn_latent", "work": ["latent_attn_work_per_step"]}


def read(ctx):
    return _roofline.share(ctx, READS["work"][0],
                           [{"scope": READS["scope"]}])
