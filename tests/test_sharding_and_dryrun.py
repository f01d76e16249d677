"""Sharding rules + dry-run machinery on a small virtual-device mesh.

The production 512-device sweep runs via ``repro.launch.dryrun``; these tests
prove the same code path (rules -> jit(in_shardings) -> lower -> compile ->
collective inventory) on an 8-device host mesh inside the test suite.
"""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.sharding import rules


def test_param_specs_shapes_divisible():
    """Every sharded dim must be divisible by its mesh axis size."""
    sizes = {"data": 16, "model": 16}
    for arch_id in ("dbrx-132b", "deepseek-v2-236b", "smollm-135m",
                    "xlstm-1.3b", "recurrentgemma-9b", "whisper-tiny"):
        cfg = get_arch(arch_id)
        from repro.models import lm
        import jax.numpy as jnp
        shapes = jax.eval_shape(
            lambda c=cfg: lm.init_params(jax.random.PRNGKey(0), c,
                                         jnp.float32))
        specs = rules.param_specs(shapes, cfg, ("data",), "model", 16, 16)

        def check(path, leaf, spec):
            for d, ax in enumerate(spec):
                if ax is None:
                    continue
                size = np.prod([sizes[a] for a in
                                (ax if isinstance(ax, tuple) else (ax,))])
                assert leaf.shape[d] % size == 0, (arch_id, path, leaf.shape,
                                                   spec)

        jax.tree_util.tree_map_with_path(
            lambda p, l, s: check(p, l, s), shapes, specs)


def test_param_specs_no_tp_when_tp_size_1():
    cfg = get_arch("smollm-135m")
    from repro.models import lm
    import jax.numpy as jnp
    shapes = jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
    specs = rules.param_specs(shapes, cfg, ("data", "model"), "model",
                              256, 1)
    for spec in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)):
        for entry in spec:
            assert entry != "model" or isinstance(entry, tuple)


_DRYRUN_SMALL = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro.config import SHAPES_BY_NAME, TrainConfig, ShapeConfig
from repro.configs import get_arch
from repro.launch.mesh import make_mesh
from repro.models import lm
from repro.sharding import rules
from repro.train.optim import adamw_update

# reduced config, small shape, 4x2 mesh — full dry-run code path
cfg = get_arch("smollm-135m", reduced=True)
shape = ShapeConfig("mini_train", 64, 8, "train")
mesh = make_mesh((4, 2))
dp, tp = ("data",), "model"
specs = lm.input_specs(cfg, shape, jnp.float32)
params_shape = jax.eval_shape(
    lambda: lm.init_params(jax.random.PRNGKey(0), cfg, jnp.float32))
pspecs = rules.param_specs(params_shape, cfg, dp, tp, 4, 2)
p_shard = jax.tree_util.tree_map(
    lambda s: jax.sharding.NamedSharding(mesh, s), pspecs)
opt_shape = {"m": params_shape, "v": params_shape,
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
opt_shard = {"m": p_shard, "v": p_shard,
             "step": jax.sharding.NamedSharding(mesh,
                                                jax.sharding.PartitionSpec())}
bspecs = rules.batch_specs(specs, dp, tp, 4)
b_shard = jax.tree_util.tree_map(
    lambda s: jax.sharding.NamedSharding(mesh, s), bspecs)
tcfg = TrainConfig()

def train_step(params, opt_state, batch):
    (loss, _), grads = jax.value_and_grad(
        lambda p: lm.loss_fn(p, batch, cfg, dtype=jnp.float32,
                             remat_policy="none"), has_aux=True)(params)
    params, opt_state, _ = adamw_update(grads, opt_state, params, tcfg)
    return params, opt_state, loss

fn = jax.jit(train_step, in_shardings=(p_shard, opt_shard, b_shard))
lowered = fn.lower(params_shape, opt_shape, specs)
compiled = lowered.compile()
mem = compiled.memory_analysis()
from repro.analysis.flops import hlo_cost_analysis
cost = hlo_cost_analysis(compiled)  # dict/list-of-dicts across jax versions

from repro.launch.dryrun import collective_inventory
inv = collective_inventory(compiled.as_text())
print(json.dumps({
    "ok": True,
    "flops": cost.get("flops", 0),
    "has_collectives": bool(inv),
    "inventory_kinds": sorted(inv),
}))
"""


def test_dryrun_code_path_small_mesh():
    out = subprocess.run([sys.executable, "-c", _DRYRUN_SMALL],
                         capture_output=True, text=True, timeout=600,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "HOME": "/root",
                              # keep libtpu from probing TPU metadata for
                              # minutes in the scrubbed subprocess env
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-3000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["ok"]
    assert payload["flops"] > 0
    assert payload["has_collectives"], payload


def test_collective_inventory_parser():
    from repro.launch.dryrun import collective_inventory
    hlo = """
ENTRY %main.1 (p0: f32[8]) -> f32[8] {
  %all-reduce.1 = f32[256,128]{1,0} all-reduce(%x), replica_groups={}
}
%while_body.2 (p: f32[8]) -> f32[8] {
  %ag = bf16[64,32]{1,0} all-gather(%y), dimensions={0}
}
"""
    inv = collective_inventory(hlo)
    assert inv["all-reduce"] == 256 * 128 * 4
    assert inv["all-gather.scanned"] == 64 * 32 * 2
