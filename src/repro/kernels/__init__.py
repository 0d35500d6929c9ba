# Pallas TPU kernels for the perf-critical compute layers, each with:
#   kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling
#   ops.py    — jit'd wrapper dispatching pallas (TPU) vs reference (CPU)
#   ref.py    — pure-jnp oracle used by tests and the CPU dry-run
