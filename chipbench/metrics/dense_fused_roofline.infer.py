"""dense_fused_roofline.infer: the dense fused structure2vec layer kernel's
share of its roofline in the solve cells (moves infer_step_ms).

The least time for the kernel's work is the larger of its operations over
the chip's peak rate and its bytes over the chip's memory bandwidth; the
share is that least time over the summed device time of the kernel's
events.  Work is counted from the operand shapes and types in each event's
HLO instruction, so a change of adjacency storage changes the count with
it:

- operations: 2*B*K*L*N for the aggregation embed (B,K,L) @ adj (B,L,N)
  plus 2*B*K*K*N for theta4 @ nbr;
- bytes: every operand read once and the output written once.
"""
from chipbench import kernels

# the Pallas call's HLO instruction (the padded-sparse and CSR kernels are
# fused_s2v_layer_sparse and fused_s2v_layer_csr)
NAMES = ("%fused_s2v_layer.",)


def work(shapes):
    """(operations, bytes) of one call from its [output, theta4, embed,
    adj, base] shapes, each (dtype, dims)."""
    out, t4, embed, adj, base = shapes
    b, k, l_ = embed[1]
    n = adj[1][2]
    ops = 2 * b * k * l_ * n + 2 * b * k * k * n
    return ops, sum(kernels.nbytes(s) for s in shapes)


def read(ctx):
    return kernels.roofline_share(ctx, NAMES, work)
