"""Device op names of the program's kernels, as the profiler shows them."""

# the fused Pallas paged-decode attention (repro.kernels.paged_attention)
PAGED_ATTENTION = r"paged_decode|decode_kernel"
