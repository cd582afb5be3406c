"""int8 cross-pod gradient compression — 8 forced host devices (2,2,2)."""
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
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models.config import ModelConfig
    from repro.models import transformer
    from repro.compat import make_mesh, use_mesh
    from repro.models.steps import make_train_step, input_specs
    from repro.train.optimizer import AdamWConfig, init_opt_state

    cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=4, d_ff=64, vocab=256,
                      dtype="float32")
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    B, S = 8, 16
    with use_mesh(mesh):
        params, _ = transformer.init_model(jax.random.PRNGKey(0), cfg,
                                           mesh.axis_names)
        state = {"params": params, "opt": init_opt_state(params)}
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, 256, (B, S)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 256, (B, S)),
                                       jnp.int32)}
        out = {}
        results = {}
        for tag, comp in (("off", None), ("int8", "int8")):
            step = jax.jit(make_train_step(cfg, AdamWConfig(),
                                           grad_compression=comp))
            lowered = step.lower(state, batch)
            compiled = lowered.compile()
            hlo = compiled.as_text()
            st2, m = compiled(state, batch)
            results[tag] = (float(m["loss"]),
                            jax.tree.leaves(st2["params"]))
            out[f"s8_allgather_{tag}"] = int("s8" in hlo and
                                             "all-gather" in hlo and
                                             hlo.count("s8[") > 0)
        l0, p0 = results["off"]
        l1, p1 = results["int8"]
        out["loss_rel_diff"] = abs(l0 - l1) / max(abs(l0), 1e-9)
        out["param_max_rel"] = max(
            float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(a)) + 1e-9))
            for a, b in zip(p0, p1))
        print(json.dumps(out))
""")


def test_int8_grad_compression():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # loss is computed pre-reduction — identical
    assert out["loss_rel_diff"] < 1e-5
    # updated params agree to quantization tolerance (one AdamW step)
    assert out["param_max_rel"] < 0.05
    # the compressed program actually moves int8 on the pod axis
    assert out["s8_allgather_int8"] == 1
