"""Least time of causal attention (two products forward and four backward over the causal half: the family's work module, `flash_attn_work_per_step`) over the device time of the three flash kernels, by their `name=`."""
from benchmark.layer_metrics import _roofline

LAYER = "dense model"
UNIT = "%"
MOVES = "train_examples_per_s"
SOURCE = "device_trace"
KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dkdv",
           "flash_attention_bwd_dq")
READS = {"kernel": "flash_attention_fwd",
         "work": ["flash_attn_work_per_step"]}


def read(ctx):
    return _roofline.share(ctx, READS["work"][0],
                           [{"kernel": name} for name in KERNELS])
