"""MLA decode attention over paged latents: TPU Pallas kernel + float32
oracle.

``mla_decode(q_lat, q_rope, ckv_pages, krope_pages, tables, lens, layer,
scale=...)``: the absorbed decode attention of every lane over its first
``lens[b]`` latent rows, read through its block table from layer
``layer`` of the stacked pools, and nothing past them.  See
docs/kernels.md.
"""

from .mla_decode import KERNEL_NAME
from .ops import mla_decode
from .ref import mla_decode as reference
