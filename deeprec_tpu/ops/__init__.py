from deeprec_tpu.ops.dedup import dedup_at_budget, resolve_size, sort_unique
from deeprec_tpu.ops.flash_attention import attention_reference, flash_attention
from deeprec_tpu.ops.fused_lookup import (
    apply_rows_sr,
    fused_gather_combine,
    gather_rows,
    stochastic_round,
)
