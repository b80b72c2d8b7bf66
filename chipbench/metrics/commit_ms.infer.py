"""commit_ms.infer: device self time under the program scope ``env.commit``
in the traced window, per policy evaluation, in the solve cells (moves
infer_step_ms).

The scope holds the environment's commit rule (for MVC: the rewrite of
the residual graph or its edge mask, the degree, the candidates and the
done check).  None when the trace holds no op under it (a program without
the scope)."""
import pathlib

from chipbench import scopes

ROOT = pathlib.Path(__file__).resolve().parents[1]


def read(ctx):
    return scopes.ms_per_eval(ctx, "env.commit", ROOT)
