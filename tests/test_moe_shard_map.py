"""Expert-parallel shard_map MoE vs the dense-dispatch oracle — run in a
subprocess with 8 forced host devices (main pytest process keeps 1 device)."""
import json
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.compat import make_mesh, use_mesh
    from repro.models.common import ParamCollector
    from repro.models.mlp import init_moe, moe_forward

    B, S, D, E, K, F = 4, 16, 32, 8, 2, 64
    mesh = make_mesh((2, 4), ("data", "model"))
    col = ParamCollector(jax.random.PRNGKey(0), dtype=jnp.float32)
    p, _ = init_moe(col, D, E, F)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D), jnp.float32)

    def loss(p, x, impl):
        y, a = moe_forward(p, x, n_experts=E, top_k=K,
                           capacity_factor=1.25, impl=impl)
        return jnp.sum(y ** 2) + 0.01 * a

    out = {}
    with use_mesh(mesh):
        y_d, a_d = jax.jit(lambda p, x: moe_forward(
            p, x, n_experts=E, top_k=K, capacity_factor=1.25,
            impl="dense"))(p, x)
        y_s, a_s = jax.jit(lambda p, x: moe_forward(
            p, x, n_experts=E, top_k=K, capacity_factor=1.25,
            impl="shard_map"))(p, x)
        out["y_maxdiff"] = float(jnp.abs(y_d - y_s).max())
        out["aux_diff"] = float(jnp.abs(a_d - a_s))
        g_d = jax.jit(jax.grad(loss), static_argnums=2)(p, x, "dense")
        g_s = jax.jit(jax.grad(loss), static_argnums=2)(p, x, "shard_map")
        out["grad_maxdiff"] = max(
            float(jnp.abs(g_d[k] - g_s[k]).max()) for k in g_d)

        # seq-sharded combine path (psum_scatter)
        y_sp, _ = jax.jit(lambda p, x: moe_forward(
            p, x, n_experts=E, top_k=K, capacity_factor=1.25,
            impl="shard_map", seq_sharded=True))(p, x)
        out["y_sp_maxdiff"] = float(jnp.abs(y_d - y_sp).max())
    print(json.dumps(out))
""")


def test_shard_map_matches_dense_oracle():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["y_maxdiff"] < 1e-5
    assert out["aux_diff"] < 1e-6
    assert out["grad_maxdiff"] < 5e-3
    assert out["y_sp_maxdiff"] < 1e-5
