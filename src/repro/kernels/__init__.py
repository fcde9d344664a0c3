# Pallas TPU kernels: the serving engine's paged-decode attention fast
# path (paged_attention.py, dispatched via layers.attention(...,
# use_kernel=True)) plus the seed flash-attention / SSM-scan / int8
# kernels.  Public surface = the jit'd wrappers in ops.py; parity
# oracles in ref.py / the model's blocked attention.  See README.md for
# the grid/BlockSpec layouts and how the backend picks compiled or
# interpret mode.
