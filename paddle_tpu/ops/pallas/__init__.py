"""Pallas TPU kernels — hand-written kernels for the few patterns where XLA's
automatic fusion underperforms (SURVEY.md §7: "Pallas kernels only where XLA
underperforms").

The reference's analogue is the hand-written CUDA kernel layer
(``paddle/fluid/operators/math/*.cu``, 108 .cu files); here almost all of
that surface is left to XLA, and only attention-style blockwise-softmax
fusions, the decode step's attention over a paged K and V cache (a slot's
live pages read where they lie, where XLA gathers every table whole), the
decode step of a power-retention layer (one pass over a recurrent state XLA
would cross three times), the decode step of a Mamba-2 layer (the same, and
an idle slot's state is not moved) and the grouped matmul of an expert layer (each hit
expert's weights read once), get custom kernels. Kernels run in interpret
mode off-TPU so tests exercise them on the CPU mesh."""

from paddle_tpu.ops.pallas.flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_bwd_block,
    flash_attention_with_lse,
)

from paddle_tpu.ops.pallas.moe import moe_gmm  # noqa: F401
from paddle_tpu.ops.pallas.paged_attention import paged_attend_step  # noqa: F401
from paddle_tpu.ops.pallas.retention import retention_step  # noqa: F401
from paddle_tpu.ops.pallas.ssm import ssm_step  # noqa: F401

__all__ = ["flash_attention", "flash_attention_bwd_block", "flash_attention_with_lse",
           "moe_gmm", "paged_attend_step", "retention_step", "ssm_step"]
