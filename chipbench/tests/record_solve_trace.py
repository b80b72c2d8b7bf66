"""Record a small chip trace of dense solves for the benchmark's tests.

    python3 chipbench/tests/record_solve_trace.py <out.xplane.pb>

Needs one TPU chip.  Solves a dense ER graph of N=1,024 nodes (rho 0.15,
weights and graph from seed 1) twice, two policy evaluations each, under
the benchmark's host spans ``bench.window`` and ``bench.solve``, with the
profiler's default options as ``run.py --trace 1`` uses them, after one
untraced solve that compiles.  ``fixtures/dense_solve.xplane.pb`` was
recorded so from the program before it named its layers,
``fixtures/dense_scoped_solve.xplane.pb`` from the program with its scopes
and host spans.
"""
import glob
import pathlib
import shutil
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax                                              # noqa: E402

from chipbench import graphs, harness                  # noqa: E402
from chipbench.drivers.solve import program_params   # noqa: E402


def main(out: str) -> int:
    harness.require_accelerator(1)
    solve = harness.import_program("repro.core.inference").solve
    params = program_params(graphs.policy_weights(1, 32, 1024))
    adj = graphs.dense_er(1024, 0.15, 1)
    kw = dict(num_layers=2, multi_node=True, rep="dense", max_evals=2)
    solve(params, adj, **kw)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.solve"):
                    solve(params, adj, **kw)
        jax.profiler.stop_trace()
        found = glob.glob(d + "/**/*.xplane.pb", recursive=True)
        shutil.copy(found[0], out)
    print(out, pathlib.Path(out).stat().st_size, "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
