"""Subprocess body for pipeline-parallel parity tests (8 host devices)."""

import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.pipeline import pipeline_apply
from repro.launch.mesh import mesh_for

N_STAGES, D, N_MICRO, MB = 4, 16, 8, 4


def _setup():
    mesh = mesh_for((N_STAGES, 2), ("stage", "dp"))
    rng = np.random.default_rng(0)
    # n_stages small MLP stages: y = tanh(x @ w + b)
    w = jnp.asarray(rng.standard_normal((N_STAGES, D, D)) * 0.3, jnp.float32)
    b = jnp.asarray(rng.standard_normal((N_STAGES, D)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.standard_normal((N_MICRO * MB, D)), jnp.float32)

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def reference(params, x):
        h = x
        for i in range(N_STAGES):
            h = stage_fn(jax.tree.map(lambda a: a[i], params), h)
        return h

    return mesh, {"w": w, "b": b}, x, stage_fn, reference


def forward():
    mesh, params, x, stage_fn, reference = _setup()
    want = reference(params, x)
    got = pipeline_apply(stage_fn, params, x, mesh=mesh, axis="stage",
                         n_micro=N_MICRO)
    err = float(jnp.max(jnp.abs(got - want)))
    print(json.dumps({"max_err": err}))


def grad():
    mesh, params, x, stage_fn, reference = _setup()

    def loss_pp(p):
        y = pipeline_apply(stage_fn, p, x, mesh=mesh, axis="stage",
                           n_micro=N_MICRO)
        return jnp.mean(y ** 2)

    def loss_ref(p):
        return jnp.mean(reference(p, x) ** 2)

    g_pp = jax.grad(loss_pp)(params)
    g_ref = jax.grad(loss_ref)(params)
    errs = []
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_ref)):
        denom = float(jnp.max(jnp.abs(b))) + 1e-9
        errs.append(float(jnp.max(jnp.abs(a - b))) / denom)
    print(json.dumps({"max_rel_err": max(errs)}))


if __name__ == "__main__":
    assert len(jax.devices()) == 8
    {"forward": forward, "grad": grad}[sys.argv[1]]()
