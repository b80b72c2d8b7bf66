"""collective_ms.infer: device self time under the program scope
``mesh.collective`` in the traced window, per policy evaluation, averaged
over the devices, in the solve cells whose graph is split over chips
(moves infer_step_ms).

The scope wraps each collective of a row-split evaluation: the per-layer
sum of the (B, K, N) partial aggregations and the Q head's (B, K) sum over
the chips, and the gathers of the scores and of the degree and the done
check's sum in the commit.  ``scopes.py`` counts its own scopes only, so
these ops still count under ``s2v.embed``, ``q.head`` or ``env.commit``
there; this reader walks the join's instructions itself and takes those
whose ``op_name`` has the scope as a whole path component.  None when no
op carries the scope (a program without it)."""
import os
import pathlib

from chipbench import scopes

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCOPE = "mesh.collective"


def seconds(scoped) -> float:
    """Device seconds of the instructions under the scope."""
    return sum(s for s, op_name in scoped.ops.values()
               if SCOPE in (op_name or "").split("/"))


def read(ctx):
    evals = ctx.window.counts.get("evals")
    path = scopes.cell_trace(ROOT, ctx.cell.get("name", ""))
    if not evals or path is None or ctx.trace is None:
        return None
    st = os.stat(path)
    # the join that s2v_ms.infer and commit_ms.infer read, made once
    scoped = scopes._cached_join(path, ctx.trace.n_devices,
                                 (st.st_mtime_ns, st.st_size))
    s = seconds(scoped)
    if s <= 0:
        return None
    return s * 1e3 / evals
