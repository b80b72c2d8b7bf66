"""Shared helpers for the paper-figure benchmarks.

Benchmarks run on this CPU container; sizes are scaled down from the paper's
Summit node where noted (each module records the scale factor in its output).
Results are written as CSV rows (name, us_per_call, derived) plus per-figure
data files under experiments/bench/.
"""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

OUT = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "bench"


def save(name: str, obj, quick: bool = False) -> None:
    """Write a benchmark's JSON artifact under experiments/bench/.

    Quick (CI-smoke) runs land in ``<name>_quick.json`` (gitignored) so
    they can never clobber the committed full-run artifacts that carry
    the repo's acceptance claims (DESIGN.md §5.2/§13, ROADMAP exit bars).
    """
    OUT.mkdir(parents=True, exist_ok=True)
    stem = f"{name}_quick" if quick else name
    (OUT / f"{stem}.json").write_text(json.dumps(obj, indent=1))


def cpu_child(module: str, spec: dict, devices: int) -> dict:
    """Run ``python -m benchmarks.<module> --child <spec>`` on ``devices``
    forced host-CPU devices and return the JSON of its last stdout line,
    or ``{"error": ...}``.  Parents that orchestrate CPU children never
    touch JAX themselves, so on a machine with an accelerator no row of
    one result lands on the chip while its siblings run on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=os.pathsep.join(
                   ["src", os.environ.get("PYTHONPATH", "")]).rstrip(
                       os.pathsep))
    child = subprocess.run(
        [sys.executable, "-m", f"benchmarks.{module}", "--child",
         json.dumps(spec)],
        capture_output=True, text=True, env=env, timeout=1200)
    if child.returncode != 0:              # record, don't hide, failures
        return {"error": child.stderr[-1000:]}
    try:
        return json.loads(child.stdout.strip().splitlines()[-1])
    except (IndexError, json.JSONDecodeError):
        return {"error": "no JSON payload on child stdout: "
                + (child.stdout + child.stderr)[-800:]}


def platform() -> dict:
    """The device a benchmark row ran on, as JAX reports it."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "device_kind": d.device_kind,
            "device_count": jax.device_count()}


def timed(fn, *args, repeat: int = 3, **kw):
    fn(*args, **kw)                    # warmup / compile
    t0 = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt


def trained_agent(n: int = 20, kind: str = "er", steps: int = 250,
                  seed: int = 0, tau: int = 2, k: int = 16,
                  lr: float = 1e-3):
    """Train a small MVC agent (shared by several benchmarks)."""
    from repro.core import Agent, PolicyConfig, train_agent
    from repro.core.graphs import random_graph_batch
    kw = {"rho": 0.15} if kind == "er" else {"d": 4}
    train = random_graph_batch(kind, n, 8, seed=seed, **kw)
    cfg = PolicyConfig(embed_dim=k, num_layers=2, minibatch=32,
                       replay_capacity=5000, learning_rate=lr,
                       eps_decay_steps=steps // 2)
    agent = Agent(cfg, num_nodes=n)
    train_agent(agent, train, episodes=10_000, tau=tau, eval_every=10 ** 9,
                max_steps=steps, seed=seed)
    return agent
