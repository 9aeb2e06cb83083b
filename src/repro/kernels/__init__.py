"""Pallas TPU kernels for the paper's compute hot-spots: OnPair16 parsing
(longest prefix matching) and decompression — with ops.py jit wrappers and
ref.py pure-jnp oracles. The kernels compile for a TPU and run interpreted
on the CPU (platform.py); the tests check them on the CPU and compile them
for a described v5e chip."""
