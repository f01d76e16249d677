"""Distributed ForestFlow on an 8-device mesh (host-device simulation).

Rows are sharded on the `data` axis, (timestep) ensembles on the `model`
axis, and histogram accumulation psums across the data axis — the same
program the multi-pod dry-run lowers for 512 chips.

    PYTHONPATH=src python examples/distributed_forest.py
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ForestConfig
from repro.forest.distributed import make_distributed_fit
from repro.forest.packed import PackedForest, predict_forest
from repro.launch.mesh import make_mesh


def main():
    print(f"devices: {len(jax.devices())}")
    mesh = make_mesh((4, 2))
    rng = np.random.default_rng(0)
    n, p = 1024, 6
    mu = rng.normal(size=p).astype(np.float32)
    X = (mu + 0.4 * rng.normal(size=(n, p))).astype(np.float32)
    mn, mx = X.min(0), X.max(0)
    Xs = (X - mn) / (mx - mn) * 2 - 1

    fcfg = ForestConfig(n_t=8, duplicate_k=8, n_trees=12, max_depth=4,
                        n_bins=32, reg_lambda=1.0)
    fit = make_distributed_fit(mesh, fcfg, data_axes=("data",))

    n_ens = fcfg.n_t
    ts = jnp.linspace(0.0, 1.0, n_ens)
    ys = jnp.zeros((n_ens,), jnp.int32)
    keys = jax.random.split(jax.random.PRNGKey(0), n_ens * 2)
    keys = jnp.asarray(np.asarray(keys, np.uint32).reshape(n_ens, 2, 2))

    print("training 8 ensembles across the model axis, rows sharded 4-way...")
    res = fit(jnp.asarray(Xs), jnp.ones((n,), jnp.float32),
              jnp.zeros((n,), jnp.int32), ts, ys, keys)

    # generate from the distributed ensembles (flow Euler, host-side loop)
    h = 1.0 / (n_ens - 1)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (512, p)))
    for i in range(n_ens - 1, 0, -1):
        f = PackedForest(jnp.asarray(res.feat[i]),
                         jnp.asarray(res.thr_val[i]),
                         jnp.asarray(res.leaf[i]), False)
        x = x - h * np.asarray(predict_forest(jnp.asarray(x), f, 4))
    gen = (x + 1) / 2 * (mx - mn) + mn
    print("true mean:", np.round(mu, 2))
    print("gen  mean:", np.round(gen.mean(0), 2))
    print("gen  std :", np.round(gen.std(0), 2), "(true 0.4)")


if __name__ == "__main__":
    main()
