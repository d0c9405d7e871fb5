import os
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_pipeline_matches_sequential():
    """4-stage GPipe over 8 host devices == sequential reference (fp32)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys
        sys.path.insert(0, "src")
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.dist.pipeline import pipeline_forward

        n_stages, n_micro, mb, d = 4, 6, 2, 16
        mesh = make_mesh((n_stages,), ("stage",))
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.normal(size=(n_stages, d, d)) * 0.3, jnp.float32)
        params = {"w": w}
        x = jnp.asarray(rng.normal(size=(n_micro, mb, d)), jnp.float32)

        def stage_fn(p, a):
            return jnp.tanh(a @ p["w"])

        out = pipeline_forward(stage_fn, params, x, mesh, axis="stage")

        ref = x
        for s in range(n_stages):
            ref = jnp.tanh(ref @ w[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
        print("PIPELINE_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, cwd=os.path.dirname(os.path.dirname(
                           os.path.abspath(__file__))))
    assert "PIPELINE_OK" in r.stdout, r.stdout + r.stderr
