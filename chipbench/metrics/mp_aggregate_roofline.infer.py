"""mp_aggregate_roofline.infer: the aggregation-only dense kernel's share
of its roofline in the solve cells whose graph is split by rows over chips
(moves infer_step_ms).

On a row-split graph each device runs ``mp_aggregate`` on its own rows:
the (B, K, Nl) embedding of its Nl resident nodes times its (B, Nl, N)
adjacency rows, a (B, K, N) partial that the chips then sum.  The least
time of that work is the larger of its operations over the chip's peak
rate and its bytes over the chip's memory bandwidth; the share is that
least time over the device time of the kernel's events, averaged over the
devices.  Work is counted from the shapes in each event's HLO instruction:

- operations: 2*B*K*Nl*N;
- bytes: every operand read once and the output written once.
"""
from chipbench import kernels

# the Pallas call's HLO instruction, named after its jit wrapper
NAMES = ("%mp_aggregate.",)


def work(shapes):
    """(operations, bytes) of one call from its [output, embed, adj]
    shapes, each (dtype, dims)."""
    _out, embed, adj = shapes
    b, k, nl = embed[1]
    n = adj[1][2]
    return 2 * b * k * nl * n, sum(kernels.nbytes(s) for s in shapes)


def read(ctx):
    return kernels.roofline_share(ctx, NAMES, work)
