"""``decode_mfu`` on the tensor-parallel cell: useful decode FLOPs over
the decode step's (``jit_step``) device time summed over the cell's four
devices, as a share of one chip's bf16 peak, %; that is, of the four
chips' summed peak."""

from bench.metrics.decode_mfu import read  # noqa: F401
