"""Distributed forest training: shard_map + psum histograms on 8 virtual
devices. Runs in a subprocess because XLA_FLAGS must be set before jax init."""
import json
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ForestConfig
from repro.forest.distributed import make_distributed_fit
from repro.forest.packed import PackedForest, predict_forest
from repro.launch.mesh import make_mesh

assert len(jax.devices()) == 8
mesh = make_mesh((4, 2))

rng = np.random.default_rng(0)
n, p = 512, 4
mu = np.array([1.0, -1.0, 0.5, 0.0], np.float32)
X = (mu + 0.4 * rng.normal(size=(n, p))).astype(np.float32)
# scale to [-1, 1] like the host trainer does
mn, mx = X.min(0), X.max(0)
Xs = (X - mn) / (mx - mn) * 2 - 1

fcfg = ForestConfig(n_t=4, duplicate_k=8, n_trees=10, max_depth=3, n_bins=16,
                    reg_lambda=1.0)
fit = make_distributed_fit(mesh, fcfg, data_axes=("data",))

n_ens = 4  # = n_t, single class, sharded over model axis (2)
ts = jnp.linspace(0.0, 1.0, n_ens)
ys = jnp.zeros((n_ens,), jnp.int32)
keys = jax.random.split(jax.random.PRNGKey(0), n_ens * 2)
keys = jnp.asarray(np.asarray(keys, np.uint32).reshape(n_ens, 2, 2))

res = fit(jnp.asarray(Xs), jnp.ones((n,), jnp.float32),
          jnp.zeros((n,), jnp.int32), ts, ys, keys)
feat = np.asarray(res.feat)      # [n_ens, n_sub, T, H]
leaf = np.asarray(res.leaf)
assert feat.shape == (n_ens, p, 10, 7), feat.shape
assert np.all(np.isfinite(leaf))

# the t=0 ensemble regresses x1 - x0 given x_t = x0: its prediction at the
# data mean should be close to E[x1 - x0 | x0 = mean] = -mean (x1 is N(0,I))
f0 = PackedForest(jnp.asarray(res.feat[0]), jnp.asarray(res.thr_val[0]),
                  jnp.asarray(res.leaf[0]), False)
x_query = jnp.asarray(Xs.mean(0, keepdims=True))
v = np.asarray(predict_forest(x_query, f0, 3))[0]
target = -np.asarray(Xs.mean(0))
err = np.abs(v - target).max()
assert err < 0.35, (v, target)
print(json.dumps({"ok": True, "err": float(err)}))
"""


@pytest.mark.slow
@pytest.mark.distributed
def test_distributed_fit_8dev():
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                         text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ok"]


_SAMPLE_SHARDED = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import numpy as np

from repro.config import ForestConfig
from repro.data.tabular import two_moons
from repro.launch.mesh import make_mesh
from repro.launch.serve_forest import ForestServer
from repro.tabgen import fit_artifacts, sample

X, y = two_moons(300, seed=0)
fcfg = ForestConfig(n_t=5, duplicate_k=6, n_trees=8, max_depth=3, n_bins=16,
                    reg_lambda=1.0)
art = fit_artifacts(X, y, fcfg, seed=0)
mesh = make_mesh((4, 2))

# sharded == single-device, bit-for-bit under a fixed seed (noise is drawn
# per (class, row) counter, so the partitioning cannot change values);
# n=151 keeps the row shards uneven on purpose
G1, y1 = sample(art, 151, seed=1)
G2, y2 = sample(art, 151, seed=1, mesh=mesh)
assert np.array_equal(y1, y2)
np.testing.assert_allclose(G1, G2, rtol=1e-5, atol=1e-5)

# pre-sharded artifacts (the serving placement) agree too
G3, _ = sample(art.shard(mesh), 151, seed=1, mesh=mesh)
np.testing.assert_allclose(G1, G3, rtol=1e-5, atol=1e-5)

# the kernel path composes with the mesh
G4, _ = sample(art, 151, seed=1, mesh=mesh, impl="pallas_interpret")
np.testing.assert_allclose(G1, G4, rtol=1e-5, atol=1e-5)

# a class count that does not divide the model axis degrades to replicated
# classes instead of failing
y3 = np.arange(300) % 3
art3 = fit_artifacts(X, y3, fcfg, seed=0)
Ga, _ = sample(art3, 100, seed=4)
Gb, _ = sample(art3, 100, seed=4, mesh=mesh)
np.testing.assert_allclose(Ga, Gb, rtol=1e-5, atol=1e-5)

# the mesh-backed server serves micro-batched requests on the same programs
server = ForestServer(art, buckets=(64, 256), mesh=mesh)
server.warmup()
futs = [server.submit(n) for n in (17, 40, 90)]
for n, f in zip((17, 40, 90), futs):
    Xs, ys = f.result(timeout=300)
    assert Xs.shape == (n, 2)
server.stop()
assert server.stats["requests"] == 3
print(json.dumps({"ok": True}))
"""


@pytest.mark.slow
@pytest.mark.distributed
def test_sharded_sample_matches_single_8dev():
    out = subprocess.run([sys.executable, "-c", _SAMPLE_SHARDED],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ok"]
