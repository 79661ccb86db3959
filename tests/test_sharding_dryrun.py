"""Sharding rules + dry-run machinery tests.

The multi-device cases run in a SUBPROCESS with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (a miniature of the
512-device production dry-run) so the main test process keeps 1 device.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import (
    LM_RULES, axis_rules, enforce_divisible, logical_spec, make_mesh,
    param_spec)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_logical_spec_no_rules_is_empty():
    assert logical_spec(("batch", "seq")) == P()


def test_logical_spec_drops_missing_pod_axis():
    mesh = make_mesh((1, 1), ("data", "model"))
    with axis_rules(LM_RULES, mesh):
        spec = logical_spec(("batch", "seq", "heads"), mesh)
    # batch -> ("pod","data") but mesh has no "pod": reduced to "data"
    assert spec == P("data", None, "model")


def test_enforce_divisible_replicates_uneven():
    mesh = make_mesh((1, 1), ("data", "model"))
    # fake 16-wide axes by building the spec directly
    spec = P("data", "model")
    out = enforce_divisible(spec, (7, 8), mesh)   # axes are size 1 -> fine
    assert out == P("data", "model")


def test_param_spec_paths():
    mesh = make_mesh((1, 1), ("data", "model"))
    with axis_rules(LM_RULES, mesh):
        # attention projections: last dim on model
        assert param_spec("layers/groups/p0_attn_mlp/attn/wq", 2, stacked=False)[-1] == "model"
        # stacked scan params: leading layer dim never sharded
        s = param_spec("layers/groups/p0_attn_mlp/attn/wq", 3, stacked=True)
        assert s[0] is None
        # optimizer prefix still matches
        s2 = param_spec("m/layers/groups/p0_attn_mlp/mlp/w_down", 3, stacked=True)
        assert s2[0] is None
        # norm scales replicated
        assert param_spec("layers/groups/p0_attn_mlp/ln1_scale", 1) == P()
        # embeddings: vocab on model
        assert param_spec("embed/tokens", 2)[0] == "model"


_SUBPROCESS_SRC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config, reduced, ShapeSpec
    from repro.dist.sharding import (
        LM_RULES, axis_rules, make_mesh, param_shardings)
    from repro.models import build
    from repro.analysis.hlo import collective_bytes
    from repro.analysis.roofline import roofline_terms

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    out = {}
    for arch in ["qwen3-0.6b", "mamba2-780m", "phi3.5-moe-42b-a6.6b"]:
        cfg = reduced(get_config(arch), d_model=64, n_heads=4, n_kv_heads=2,
                      vocab_size=256)
        bundle = build(cfg)
        with axis_rules(LM_RULES, mesh), mesh:
            pshapes = jax.eval_shape(bundle.init_params,
                                     jax.ShapeDtypeStruct((2,), jnp.uint32))
            p_sh = param_shardings(pshapes, mesh)
            oshapes = jax.eval_shape(bundle.init_opt, pshapes)
            o_sh = param_shardings(oshapes, mesh)
            sds = jax.ShapeDtypeStruct
            batch = {"tokens": sds((8, 16), jnp.int32),
                     "labels": sds((8, 16), jnp.int32)}
            from jax.sharding import NamedSharding, PartitionSpec as P
            b_sh = {k: NamedSharding(mesh, P(("pod", "data"))) for k in batch}
            lowered = jax.jit(bundle.train_step,
                              in_shardings=(p_sh, o_sh, b_sh, None),
                              out_shardings=(p_sh, o_sh, None)).lower(
                pshapes, oshapes, batch, sds((), jnp.int32))
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            terms = roofline_terms(compiled)
            out[arch] = {
                "arg_bytes": int(mem.argument_size_in_bytes),
                "collective_bytes": terms["collective_bytes"],
                "flops": terms["hlo_flops"],
            }
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_subprocess_multidevice_lower_compile():
    """Miniature production dry-run: 8 placeholder devices, (2,2,2) pod mesh,
    three families lower + compile with sharded params/opt/batch, and the
    roofline machinery extracts nonzero flops and collective bytes."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    proc = subprocess.run([sys.executable, "-c", _SUBPROCESS_SRC],
                          capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for arch, rec in out.items():
        assert rec["arg_bytes"] > 0, arch
        assert rec["flops"] > 0, arch
        # DP grad sync means at least one collective must appear
        assert rec["collective_bytes"] > 0, arch


def test_elastic_checkpoint_restore_onto_mesh(tmp_path):
    """Checkpoints store global arrays; restore re-shards them onto whatever
    mesh the new job runs (elastic resume). 4-device subprocess."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    src = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import checkpoint as ckpt
        from repro.dist.sharding import make_mesh

        tree = {{"w": jnp.arange(32.0).reshape(8, 4), "b": jnp.ones((4,))}}
        ckpt.save({str(tmp_path)!r}, 3, tree)

        mesh = make_mesh((4,), ("data",))
        shardings = {{"w": NamedSharding(mesh, P("data")),
                      "b": NamedSharding(mesh, P())}}
        restored, step, _ = ckpt.restore({str(tmp_path)!r}, tree,
                                         shardings=shardings)
        assert step == 3
        assert restored["w"].sharding.is_equivalent_to(shardings["w"], 2)
        np.testing.assert_allclose(np.asarray(restored["w"]), np.asarray(tree["w"]))
        print("ELASTIC_OK")
    """)
    proc = subprocess.run([sys.executable, "-c", src], capture_output=True,
                          text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "ELASTIC_OK" in proc.stdout


def test_hlo_collective_parser_on_psum():
    """Parser sanity on a real compiled module containing an all-reduce."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    src = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.analysis.hlo import collective_bytes
        from repro.dist.sharding import make_mesh
        mesh = make_mesh((4,), ("data",))
        x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
        def f(a):
            return jax.lax.with_sharding_constraint(
                a.sum() * jnp.ones_like(a), NamedSharding(mesh, P()))
        with mesh:
            c = jax.jit(f, in_shardings=NamedSharding(mesh, P("data"))).lower(x).compile()
        cb = collective_bytes(c.as_text())
        print(cb["total"])
    """)
    proc = subprocess.run([sys.executable, "-c", src],
                          capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-2000:]
    total = float(proc.stdout.strip().splitlines()[-1])
    assert total > 0
