"""Pallas TPU kernels for hot ops.

The reference keeps its hand-written kernel substrate in
`paddle/fluid/operators/math/*.cu` and `paddle/cuda/src/hl_*.cu`; here the
equivalent role is played by Pallas kernels that XLA cannot synthesize as
well on its own (flash attention's online-softmax tiling, primarily).
Everything else rides XLA fusion.

The package exports no name: `flash_attention` (the training and prefill
kernels), `paged_attention` (the decode kernels over a paged cache),
`ssd_update` (the Mamba-2 state update), `expert_matmul` (the experts'
grouped matmul where XLA's tile or row walk is the slower), `fused_conv` and
`fused_lstm` are its modules, and a caller imports the
one it needs.
"""
