"""int8 error-feedback compressed psum (subprocess: needs >1 device)."""

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_compressed_psum_subprocess():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import sys, json
        sys.path.insert(0, "src")
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import AxisType
        from repro.optim.compress import compressed_psum, ef_init

        mesh = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
        rng = np.random.default_rng(0)
        # per-shard local grads: stack along axis that shard_map splits? --
        # replicated arrays with per-device values need vmap-style setup;
        # emulate by running the quantizer math directly per member and
        # checking error-feedback convergence of the MEAN over steps.
        g_true = jnp.asarray(rng.normal(size=(64, 64)), jnp.float32)
        with jax.set_mesh(mesh):
            ef = ef_init({"g": g_true})
            acc = jnp.zeros_like(g_true)
            for _ in range(30):
                out, ef = compressed_psum({"g": g_true}, ef, mesh, ("data",))
                acc = acc + out["g"]
            mean = acc / 30
        err = float(jnp.max(jnp.abs(mean - g_true)))
        rel = err / float(jnp.max(jnp.abs(g_true)))
        print(json.dumps({"rel": rel}))
    """)
    out = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        cwd=pathlib.Path(__file__).parent.parent, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    # error feedback: time-averaged compressed gradient converges to the truth
    assert res["rel"] < 0.01, res
