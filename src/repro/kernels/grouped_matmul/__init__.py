"""Grouped expert FFN over expert-sorted rows: TPU Pallas kernel + jnp
oracle.

``grouped_ffn(x, w_gate, w_up, w_down, group_sizes, layer)`` with x [m,
d] rows sorted by group and one SwiGLU expert per group, the weights a
layer stack read at ``layer``: each group's rows through its own
expert's three matmuls, fused; rows past the groups are not computed.
See docs/kernels.md.
"""

from .grouped_matmul import KERNEL_NAME
from .ops import grouped_ffn
from .ref import grouped_ffn as reference
