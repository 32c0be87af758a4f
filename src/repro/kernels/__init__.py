"""Pallas TPU kernel packages, one per compute hot-spot.

Layout convention (see docs/kernels.md): each package holds ``<name>.py``
(the Pallas kernel), ``ref.py`` (a pure-jnp oracle with identical
semantics), and ``ops.py`` (the jit'd public wrapper: native Pallas on TPU,
interpret mode elsewhere, and ``ValueError`` for a shape the kernel cannot
take — the oracle is only ever called explicitly).

Packages: ``flash_attention`` (fused train/prefill attention),
``paged_attention`` (block-table decode attention over the physical paged
KV cache), ``mla_decode`` (MLA decode attention over each lane's live
latent pages), ``grouped_matmul`` (grouped expert FFN), ``ssd_scan``
(Mamba-2 chunked scan), ``rglru_scan`` (Griffin gated linear
recurrence).
"""
