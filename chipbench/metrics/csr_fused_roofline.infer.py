"""csr_fused_roofline.infer: the fused CSR structure2vec layer kernel's
share of its roofline in the solve cells (moves infer_step_ms).

The least time is that of the work the layer needs on flat sorted edges:
operations 2*K*E for the gather-weight-sum over the E edge slots plus
2*K*K*N for theta4 @ nbr; bytes every operand read once and the output
written once.  Shapes and types come from each event's HLO instruction
(theta4 (K,K) first, the edge arrays the largest s32 operand, the output
K*N values).  The kernel computes more than this (ROADMAP S2: one-hot
matmuls of O(K*E*N)), so the share shows how far it is from the edges'
own cost.
"""
from chipbench import kernels

# the Pallas call's HLO instruction (not fused_s2v_layer.N, the dense one)
NAMES = ("%fused_s2v_layer_csr.",)


def work(shapes):
    """(operations, bytes) of one call from its [output, theta4, ...]
    shapes, each (dtype, dims)."""
    out, t4 = shapes[0], shapes[1]
    k = t4[1][0]
    n = kernels.nbytes(("s8", out[1])) // k
    e = max(kernels.nbytes(("s8", s[1])) for s in shapes if s[0] == "s32")
    ops = 2 * k * e + 2 * k * k * n
    return ops, sum(kernels.nbytes(s) for s in shapes)


def read(ctx):
    return kernels.roofline_share(ctx, NAMES, work)
