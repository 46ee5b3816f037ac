"""Multi-device correctness: runs subprocesses with 8 forced host devices
(the main test process must keep the default single device)."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _run_in_devices(py_body: str, n_devices: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.join(_REPO, "src") + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", py_body], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    body = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.configs import registry
from repro.models import build, sharding
from repro.launch.mesh import make_test_mesh
from repro.training import AdamWConfig
from repro.training.trainer import init_train_state, make_train_step

cfg = registry.get("olmo-1b").tiny()
model = build(cfg)
rng = np.random.default_rng(0)
batch = {
    "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32),
    "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 32)), jnp.int32),
}
state = init_train_state(model, jax.random.PRNGKey(0))
step = make_train_step(model, AdamWConfig(warmup_steps=1))

# single device
s1, m1 = jax.jit(step)(state, batch)
loss1 = float(m1["loss"])

# sharded: 2x2 mesh, rules installed
mesh = make_test_mesh(2, 2)
with mesh, sharding.use_rules(mesh, {"embed": None}):
    s2, m2 = jax.jit(step)(state, batch)
    loss2 = float(m2["loss"])

pa = jax.tree_util.tree_leaves(s1.params)
pb = jax.tree_util.tree_leaves(s2.params)
maxdiff = max(
    float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))))
    for a, b in zip(pa, pb)
)
print(json.dumps({"loss1": loss1, "loss2": loss2, "maxdiff": maxdiff}))
"""
    res = _run_in_devices(body)
    assert abs(res["loss1"] - res["loss2"]) < 5e-3, res
    assert res["maxdiff"] < 5e-2, res


@pytest.mark.slow
def test_sequence_parallel_decode_matches_plain():
    body = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from repro.configs import registry
from repro.models import build, sharding
from repro.launch.mesh import make_test_mesh
from repro.serving.kv_layout import alloc_caches

cfg = registry.get("olmo-1b").tiny()
model = build(cfg)
rng = np.random.default_rng(1)
params = model.init_params(jax.random.PRNGKey(0))
T, B, CAP = 24, 2, 32
tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, T)), jnp.int32)
logits, caches = model.prefill(params, {"tokens": tokens}, pad_to=CAP)
tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]

# plain (single device)
l1, _ = jax.jit(model.decode_step)(params, tok, caches)

# sequence-parallel: cache S-axis sharded over "model" (SP decode path)
mesh = make_test_mesh(2, 2)
with mesh, sharding.use_rules(mesh, {"embed": None, "kv_seq_decode": "model"}):
    l2, _ = jax.jit(model.decode_step)(params, tok, caches)

d = float(jnp.max(jnp.abs(l1.astype(jnp.float32) - l2.astype(jnp.float32))))
print(json.dumps({"maxdiff": d}))
"""
    res = _run_in_devices(body)
    assert res["maxdiff"] < 5e-2, res


@pytest.mark.slow
def test_compressed_psum_means_correctly():
    body = r"""
import json
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.training.grad_compress import compressed_psum

mesh = make_test_mesh(4, 2)
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(8, 16)), jnp.float32)

def f(x):
    return compressed_psum(x, "data")

y = jax.shard_map(f, mesh=mesh, in_specs=P("data", None), out_specs=P("data", None))(x)
# exact mean over the data axis
ref = jnp.broadcast_to(x.reshape(4, 2, 16).mean(axis=0, keepdims=True), (4, 2, 16)).reshape(8, 16)
err = float(jnp.max(jnp.abs(y - ref)))
scale = float(jnp.abs(x).max() / 127.0)
print(json.dumps({"err": err, "bin": scale}))
"""
    res = _run_in_devices(body)
    # int8 wire: error bounded by one quantization bin
    assert res["err"] <= res["bin"] * 1.01 + 1e-7, res
