"""`chip_smoke.py` off the chip: its phase functions at tiny sizes on the
CPU with interpret-mode kernels, its refusal to run without a TPU, and
the compile-cache helper it calls."""
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

from repro import compile_cache  # noqa: E402


def test_main_refuses_without_tpu(capsys):
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_phase_kernels_match_oracles_in_interpret_mode():
    errors = chip_smoke.phase_kernels(batch=2, n=48, rho=0.2, ba_degree=3,
                                      seed=0, interpret=True)
    assert set(errors) >= set(chip_smoke.F32_TOL)


@pytest.mark.parametrize("rep", ["dense", "sparse", "csr"])
def test_phase_train(rep):
    kw = {"rho": 0.2} if rep == "dense" else {"d": 3}
    out = chip_smoke.phase_train(rep, graphs=8, n=24, steps=10, tau=2,
                                 seed=0, **kw)
    assert out["param_delta"] > 0 and out["impl"] == "xla"


def test_phase_solve_dense_capped():
    out = chip_smoke.phase_solve_dense(n=64, rho=0.15, max_evals=3, seed=0)
    assert out["ms_per_eval"] > 0


def _ba_csr_uncached(n, d, *, seed):
    from repro.core.graphs import barabasi_albert_edges, csr_from_edges
    return csr_from_edges(n, *barabasi_albert_edges(n, d, seed=seed))


@pytest.mark.parametrize("rep", ["sparse", "csr"])
def test_phase_solve_ba(rep, monkeypatch):
    # the phase's on-disk graph cache stays out of the checkout here
    monkeypatch.setattr(chip_smoke, "cached_ba_csr", _ba_csr_uncached)
    out = chip_smoke.phase_solve_ba(rep, n=128, d=3, max_d=8, seed=0)
    assert out["evals"] > 0 and out["ratio"] > 0


def test_phase_serve():
    out = chip_smoke.phase_serve(requests=5, min_n=6, max_n=20, seed=0)
    assert out["wall_s"] > 0


def test_mesh_phases_on_four_cpu_devices():
    """The ``--chips 4`` phases on a forced 4-device CPU host: mesh
    scores, capped solve and both train meshes match one device."""
    code = ("import chip_smoke as cs\n"
            "cs.phase_mesh_solve(n=32, rho=0.2, spatial=(1, 4), "
            "max_evals=3, seed=0)\n"
            "for spec, coll in (((2, 2), 'manual'), ((4, 1), 'auto')):\n"
            "    cs.phase_mesh_train(spatial=spec, collectives=coll, "
            "graphs=8, n=16, steps=10, tau=2, seed=0)\n"
            "print('MESH_OK')\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                         capture_output=True, text=True, timeout=600)
    tail = (out.stdout + out.stderr)[-3000:]
    assert out.returncode == 0, tail
    assert "MESH_OK" in out.stdout, tail
    assert "shards dense adj at (1, 4)" in out.stdout, tail


@pytest.fixture
def cache_config():
    """Restore the global jax cache settings the helper changes."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in names}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_env_var_wins(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_repo_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.setup_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want

