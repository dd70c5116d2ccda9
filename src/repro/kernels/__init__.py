# Pallas TPU kernels for the framework's compute hot spots, each with a
# jit'd wrapper (ops.py) and a pure-jnp oracle (ref.py):
#   flash_attention   online-softmax attention (q/kv block grid, VMEM scratch)
#   rmsnorm           fused row-blocked RMSNorm
#   mamba_scan        Mamba2 SSD intra-chunk compute + carried state
#   quant             int8 block quantize / fused dequant-add (compressed sync)
# Kernels are TPU targets.  The CPU tests pass interpret=True explicitly and
# sweep shapes/dtypes against the oracles; tests/test_chip_compile.py compiles
# the quant kernels for a described v5e chip.  The model's training attention
# on a TPU is JAX's own splash attention (models.layers.fused_causal_attention),
# not flash_attention here.
