"""Functional op library.

TPU-native replacement for the reference operator zoo
(``paddle/fluid/operators/`` — ~250 op families × CPU/CUDA kernels, §2.1 of
SURVEY.md). Here every op is a pure jax.numpy/lax composition; XLA fuses and
tiles them onto MXU/VPU, so there is no kernel registry, no OpKernelType
dispatch (reference ``framework/op_registry.h:38-150``), and no per-op data
transform (``operator.cc:750``). Pallas kernels (``paddle_tpu.ops.pallas``)
are used only where XLA underperforms.
"""

from paddle_tpu.ops.math import *  # noqa: F401,F403
from paddle_tpu.ops.nn import *  # noqa: F401,F403
from paddle_tpu.ops.control_flow import *  # noqa: F401,F403
from paddle_tpu.ops.losses import *  # noqa: F401,F403
from paddle_tpu.ops.detection import *  # noqa: F401,F403
from paddle_tpu.ops.quant import *  # noqa: F401,F403
from paddle_tpu.ops import (  # noqa: F401
    math,
    nn,
    rnn,
    sequence,
    attention,
    ring_attention,
    control_flow,
    losses,
    detection,
    quant,
    moe,
)

from paddle_tpu.ops import math as _math
from paddle_tpu.ops import nn as _nn
from paddle_tpu.ops import control_flow as _cf
from paddle_tpu.ops import losses as _losses
from paddle_tpu.ops import detection as _det
from paddle_tpu.ops import quant as _quant

__all__ = (
    list(getattr(_math, "__all__", []))
    + list(getattr(_nn, "__all__", []))
    + list(_cf.__all__)
    + list(_losses.__all__)
    + list(_det.__all__)
    + list(_quant.__all__)
    + [
        "math",
        "nn",
        "rnn",
        "sequence",
        "attention",
        "ring_attention",
        "control_flow",
        "losses",
        "detection",
        "quant",
        "moe",
    ]
)
