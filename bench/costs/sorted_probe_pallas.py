"""``sorted_probe`` stage B: each bucket of queries against its table block.

Operands ``(2, M_pad)`` uint32 hi/lo table planes and ``(nblocks, 2,
qmax)`` uint32 bucketed queries; outputs two ``(nblocks, 1, qmax)``
int32.  The work is comparisons on the vector unit (no matmul), so only
the HBM bound applies: the table and the buckets read once, the outputs
written once.
"""

from bench.costs import nbytes


def cost(kernel):
    return None, nbytes(kernel.operands) + nbytes(kernel.outputs)
