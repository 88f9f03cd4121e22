"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package has a ``kernel.py`` (``pl.pallas_call`` with explicit
BlockSpec VMEM tiling) and an ``ops.py`` (the public wrapper in model
tensor layouts); ``flash_attention`` and ``block_quant`` also keep a
``ref.py``, the pure-jnp oracle they are validated against.  The SSD
intra-chunk pair (``ssd_scan``) is validated against ``ssd_chunked``'s own
jnp term, which it replaces on a TPU.

On this CPU container kernels run under ``interpret=True``; on a TPU
runtime the same calls compile to Mosaic.
"""
