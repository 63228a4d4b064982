"""Pod-scale fractal sort over every device present: local histograms,
one tapered psum merge, exact global ranks, one all_to_all — no sampling.

    PYTHONPATH=src python examples/distributed_sort.py

On a CPU host it asks for 8 placeholder devices; on an accelerator host
it uses the chips it finds.  The mesh axis is the largest power of two
that the devices cover.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.core import distributed_fractal_sort  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402

D = 1 << (len(jax.devices()).bit_length() - 1)
mesh = make_mesh((D,), ("data",))
rng = np.random.default_rng(0)

for name, keys in {
    "uniform": rng.integers(0, 1 << 16, 1 << 15).astype(np.int32),
    "zipf-skewed": np.clip(rng.zipf(1.2, 1 << 15), 0, 65535).astype(np.int32),
}.items():
    ks = jax.device_put(jnp.asarray(keys), NamedSharding(mesh, P("data")))
    out, overflow = distributed_fractal_sort(ks, mesh, "data", 16)
    ok = bool((out == jnp.sort(ks)).all())
    print(f"{name:12s}: sorted={ok} overflow={bool(overflow)} "
          f"({D} shards x {len(keys) // D} keys)")
print("distributed sort OK — same code path scales to the 16x16 pod mesh")
