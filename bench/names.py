"""Names the per-layer readers look for in a trace: the engine's two
jitted programs and the two Pallas kernels of the serving path."""
DECODE = "_step_paged_impl"             # PagedEngine._step_paged
PREFILL = "_prefill_paged_impl"         # PagedEngine._prefill_paged
QUANT_MATMUL = "quant_matmul_b"         # kernels/quant_matmul.py name=
PAGED_ATTENTION = "paged_attention_"    # kernels/paged_attention.py name=
