"""Manual (shard_map + all_to_all) MoE vs the pure-GSPMD auto path.

Runs in a subprocess with 8 placeholder devices on a (2,2,2) mesh. With a
capacity factor large enough that nothing is dropped anywhere, both paths
compute the same mathematical function, so outputs (and grads) must agree.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SRC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import dataclasses
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, reduced
    from repro.dist.sharding import LM_RULES, axis_rules, make_mesh
    from repro.models.moe import init_moe, _moe_block_auto, _moe_block_manual

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = reduced(get_config("phi3.5-moe-42b-a6.6b"))
    # no-drop capacity: local and global dispatch then agree exactly
    cfg = dataclasses.replace(cfg, capacity_factor=float(cfg.n_experts * 3))
    rng = jax.random.PRNGKey(0)
    p = init_moe(rng, cfg, jnp.float32)
    B, S, d = 4, 8, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32)

    def loss_auto(p, x):
        y, aux = _moe_block_auto(p, x, cfg)
        return (y.astype(jnp.float32) ** 2).sum() + 0.0 * aux, y

    def loss_manual(p, x):
        y, aux = _moe_block_manual(p, x, cfg, mesh)
        return (y.astype(jnp.float32) ** 2).sum() + 0.0 * aux, y

    with axis_rules(LM_RULES, mesh), mesh:
        (la, ya), ga = jax.jit(jax.value_and_grad(loss_auto, has_aux=True))(p, x)
        (lm, ym), gm = jax.jit(jax.value_and_grad(loss_manual, has_aux=True))(p, x)

    out = {
        "y_err": float(jnp.max(jnp.abs(ya - ym))),
        "loss_rel": float(abs(la - lm) / (abs(la) + 1e-9)),
        "g_err": float(max(jnp.max(jnp.abs(a - b))
                           for a, b in zip(jax.tree_util.tree_leaves(ga),
                                           jax.tree_util.tree_leaves(gm)))),
        "y_scale": float(jnp.max(jnp.abs(ya))),
    }
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_manual_moe_matches_auto():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", _SRC], capture_output=True,
                          text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    scale = max(out["y_scale"], 1e-6)
    assert out["y_err"] <= 1e-4 * scale + 1e-5, out
    assert out["loss_rel"] <= 1e-5, out
    assert out["g_err"] <= 1e-3, out
