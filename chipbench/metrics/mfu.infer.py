"""mfu.infer: the work the model needs per policy evaluation, times the
evaluations of the traced window, over the window times the chip's peak.

The count does not depend on how the program computes a layer: per
evaluation, one neighbour aggregation over the E directed edges (2*K*E;
layer 0 aggregates all-zero embeddings and needs no work), the theta3
embedding of the degree and theta4 of each later layer (2*K*K*N each), and
the Q head (theta6 2*K*K*N, theta7 2*2K*N, theta5 2*K*K).  The N*N work of
a dense adjacency is not counted.  The peak is the chip's published bf16
rate, the only one published, although the configuration computes in f32.
"""


def flops_per_eval(n: int, e: int, k: int, layers: int) -> int:
    aggregate = 2 * k * e * (layers - 1)
    embed = 2 * k * k * n * layers
    head = 2 * k * k * n + 2 * 2 * k * n + 2 * k * k
    return aggregate + embed + head


def read(ctx):
    c = ctx.window.counts
    if not c.get("evals") or ctx.window.seconds <= 0:
        return None
    f = flops_per_eval(c["nodes"], c["edges"], ctx.config["embed_dim"],
                       ctx.config["num_layers"])
    return 100.0 * f * c["evals"] / (ctx.window.seconds
                                     * ctx.peak["bf16_flops_per_s"])
