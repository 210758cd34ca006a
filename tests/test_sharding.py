"""Sharding-rules unit tests + a real multi-device dry-run on a small
host-device mesh (runs in a subprocess so the 1-device default for the
rest of the suite is preserved)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import get_config
from repro.models.model import init_model
from repro.sharding.rules import param_pspecs


def _find(tree, path):
    cur = tree
    for part in path.split("/"):
        cur = cur[part]
    return cur


def test_dense_lm_param_specs(key):
    cfg = get_config("llama3.2-1b", reduced=True)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    specs = param_pspecs(params, n_model=2, n_data=2)
    # spectral MLP: V of up is TP-row-sharded, U is FSDP-row-sharded
    assert _find(specs, "layers/mlp/up/V") == P(None, "model", None)
    assert _find(specs, "layers/mlp/up/U") == P(None, "data", None)
    assert _find(specs, "layers/mlp/down/U") == P(None, "model", None)
    assert all(a is None for a in _find(specs, "layers/mlp/up/s"))  # replicated
    # dense attention: col-shard in, row-shard out, FSDP on the other axis
    assert _find(specs, "layers/attn/wq/w") == P(None, "data", "model")
    assert _find(specs, "layers/attn/wo/w") == P(None, "model", "data")
    # embeddings vocab-sharded (128256 % 2 == 0)
    assert _find(specs, "embed/w") == P("model", "data")
    # norms replicated
    assert _find(specs, "layers/attn_norm/scale") == P()


def test_moe_expert_axis_sharded(key):
    cfg = get_config("deepseek-v3-671b", reduced=True)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    specs = param_pspecs(params, n_model=2, n_data=2)
    # expert spectral factors: (L, E, m, k) -> E over model, m over data
    assert _find(specs, "moe_layers/moe/gate/U") == P(None, "model", "data", None)
    assert _find(specs, "moe_layers/moe/router/w") == P(None, None, "model")


def test_indivisible_dims_replicate(key):
    """qwen1.5-4b heads (20) don't divide 16 -> explicit replication
    instead of a silent GSPMD gather."""
    cfg = get_config("qwen1.5-4b")
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    specs = param_pspecs(params, n_model=16, n_data=16)
    # h*hd = 2560 divides 16 -> still sharded on the flat dim
    assert _find(specs, "layers/attn/wq/w") == P(None, "data", "model")
    # granite vocab 49155 doesn't divide -> d-sharded (model) embedding,
    # vocab axis replicated (49155 also doesn't divide the data axis)
    cfg_g = get_config("granite-3-2b")
    params_g = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg_g))
    specs_g = param_pspecs(params_g, n_model=16, n_data=16)
    assert _find(specs_g, "embed/w") == P(None, "model")


_SUBPROCESS_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, json
from repro.config import get_config, SHAPES
from repro.config.shapes import ShapeSpec
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh

cfg = get_config("{arch}", reduced=True)
mesh = make_mesh((4, 2), ("data", "model"))
shape = ShapeSpec("t", 64, 8, "{kind}")
lowered = steps_mod.lower_step(cfg, shape, mesh)
compiled = lowered.compile()
cost = compiled.cost_analysis()
if isinstance(cost, (list, tuple)):  # older jax returns [dict]
    cost = cost[0] if cost else {{}}
print(json.dumps({{"flops": cost.get("flops", 0.0)}}))
"""


@pytest.mark.parametrize("arch,kind", [
    ("llama3.2-1b", "train"),
    ("deepseek-v3-671b", "train"),
    ("jamba-v0.1-52b", "train"),
    ("llama3.2-1b", "decode"),
])
def test_small_mesh_dryrun_compiles(arch, kind):
    """lower+compile the real step builders on an 8-device host mesh —
    the same code path the 512-device production dry-run uses."""
    code = _SUBPROCESS_DRYRUN.format(arch=arch, kind=kind)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["flops"] > 0


_SUBPROCESS_TRAINER_INIT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, json
import numpy as np
from repro.api import ModelSpec, RunSpec, TrainSpec, Trainer
from repro.models.model import init_model

spec = RunSpec(model=ModelSpec("smollm2-1.7b", reduced=True),
               train=TrainSpec(steps=1, batch=4, seq=16))
trainer = Trainer(spec)
state = trainer._init_state()
leaves = jax.tree.leaves(state)
per_device = {d.id: 0 for d in jax.devices()}
for x in leaves:
    for shard in x.addressable_shards:
        per_device[shard.device.id] += shard.data.nbytes
eager = trainer.optimizer.init(
    init_model(jax.random.PRNGKey(spec.train.seed), trainer.cfg))
print(json.dumps({
    "mesh": trainer.mesh.size,
    "as_specified": all(
        x.sharding.is_equivalent_to(s, x.ndim)
        for x, s in zip(leaves, jax.tree.leaves(trainer.state_shardings))),
    "per_device": list(per_device.values()),
    "total": sum(x.nbytes for x in leaves),
    "max_diff": max(float(np.max(np.abs(np.asarray(a, np.float64)
                                        - np.asarray(b, np.float64))))
                    for a, b in zip(leaves, jax.tree.leaves(eager))),
}))
"""


def test_trainer_mesh_state_born_sharded():
    """On the Trainer's automatic 4-device mesh the initial train state
    is created with the step's shardings: no device ever holds the whole
    state, and the values are the eager single-device init's."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", _SUBPROCESS_TRAINER_INIT],
                         capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["mesh"] == 4
    assert payload["as_specified"]
    assert len(payload["per_device"]) == 4
    assert max(payload["per_device"]) < payload["total"]
    assert payload["max_diff"] <= 1e-6
