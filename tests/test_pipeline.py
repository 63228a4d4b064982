"""GPipe pipeline substrate == sequential execution (subprocess, 4 devs)."""

import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_gpipe_matches_sequential():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.pipeline import gpipe_apply

        mesh = make_mesh((4,), ("stage",))
        key = jax.random.PRNGKey(0)
        S, M, mb, D = 4, 6, 2, 16
        # one linear+gelu layer per stage
        Ws = jax.random.normal(key, (S, D, D)) * 0.3

        def stage_fn(p, x):
            return jax.nn.gelu(x @ p["w"])

        x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, D))
        got = gpipe_apply(stage_fn, mesh, "stage", {"w": Ws}, x)

        want = x
        for s in range(S):
            want = jax.nn.gelu(want @ Ws[s])
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("GPIPE_OK")
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, cwd=REPO_ROOT,
                       # JAX_PLATFORMS=cpu: the image ships libtpu; without
                       # the pin jax probes for a TPU and hangs the child.
                       env={"PYTHONPATH": "src",
                            "PATH": os.environ.get("PATH", "/usr/bin:/bin"),
                            "HOME": os.environ.get("HOME", "/root"),
                            "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "GPIPE_OK" in r.stdout
