"""s2v_ms.infer: device self time under the program scope ``s2v.embed`` in
the traced window, per policy evaluation, in the solve cells (moves
infer_step_ms).

The scope holds the whole structure2vec embedding of one evaluation: the
degree and the theta1/theta2/theta3 terms, each layer (the adjacency pad
and the fused Pallas call on the dense rep; row ids, residual edge factors
and the CSR kernel on CSR).  None when the trace holds no op under it (a
program without the scope)."""
import pathlib

from chipbench import scopes

ROOT = pathlib.Path(__file__).resolve().parents[1]


def read(ctx):
    return scopes.ms_per_eval(ctx, "s2v.embed", ROOT)
