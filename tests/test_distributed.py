"""Distributed integration tests: run a real sharded train/serve step with
actual values on a small multi-device host mesh.

XLA locks the host device count at first init, so these run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 (the
dry-run owns the 512-device configuration; everything else sees 1 device).
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch
from repro.configs.shapes import InputShape
from repro.data import pipeline as data
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import build_decode_step, build_train_step
from repro.models import lm
from repro.optim import adamw

assert len(jax.devices()) == 8, jax.devices()
mesh = make_test_mesh((4, 2), ("data", "model"))

for arch_id in ["yi-6b", "mamba2-2.7b", "deepseek-v2-lite-16b"]:
    cfg = get_arch(arch_id).reduced()
    # make reduced dims divide the (4, 2) test mesh
    cfg = dataclasses.replace(cfg, vocab_size=512)
    shape = InputShape("t", 32, 8, "train")
    bundle = build_train_step(cfg, shape, mesh=mesh, remat=False,
                              microbatches=1)
    params = lm.init(cfg, jax.random.PRNGKey(0))
    opt = adamw.init(params)
    batch = data.batch_for_step(cfg, shape, 0)
    with mesh:
        p2, o2, metrics = bundle.fn(params, opt, batch)
        nll1 = float(metrics["nll"])
        p3, o3, metrics = bundle.fn(p2, o2, data.batch_for_step(cfg, shape, 1))
    assert np.isfinite(nll1), (arch_id, nll1)
    assert np.isfinite(float(metrics["nll"])), arch_id
    print(f"OK train {arch_id}: nll {nll1:.3f} -> {float(metrics['nll']):.3f}")

# shard_map MoE == single-device MoE when capacity is ample (no drops)
from repro.sharding.annotate import Sharder, profile_for
cfg = get_arch("deepseek-v2-lite-16b").reduced()
cfg = dataclasses.replace(
    cfg, vocab_size=512,
    moe=dataclasses.replace(cfg.moe, capacity_factor=16.0))
params = lm.init(cfg, jax.random.PRNGKey(3))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(4), (8, 32), 0,
                                      512)}
sharder = Sharder(mesh, profile_for(cfg), ("data",),
                  full_dp=cfg.moe is None)
with mesh:
    l_sharded = float(jax.jit(
        lambda p, b: lm.loss_fn(cfg, p, b, shard=sharder)[0])(params, batch))
l_local = float(lm.loss_fn(cfg, params, batch)[0])
assert abs(l_sharded - l_local) < 5e-2, (l_sharded, l_local)
print(f"OK moe shard_map == local: {l_sharded:.4f} vs {l_local:.4f}")

# decode step on the mesh, against the same step on one device (the
# experts: per-row capacity dispatch on the mesh, grouped products alone)
for arch_id in ["yi-6b", "deepseek-v2-lite-16b"]:
    cfg = get_arch(arch_id).reduced()
    cfg = dataclasses.replace(cfg, vocab_size=512)
    shape = InputShape("d", 32, 8, "decode")
    bundle = build_decode_step(cfg, shape, mesh=mesh)
    params = lm.init(cfg, jax.random.PRNGKey(1))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (8, 16), 0, 512)
    logits, caches = jax.jit(
        lambda p, t: lm.prefill(cfg, p, t, max_seq=32))(params, prompt)
    tok = jnp.argmax(logits, -1)
    lengths = jnp.full((8,), 16, jnp.int32)
    local, _ = jax.jit(lambda p, t, c, n: lm.decode_step(cfg, p, t, c, n))(
        params, tok, caches, lengths)
    with mesh:
        out, _ = bundle.fn(params, tok, caches, lengths)
    out, local = np.asarray(out, np.float32), np.asarray(local, np.float32)
    assert np.isfinite(out).all(), arch_id
    err = float(np.abs(out - local).max() / np.abs(local).max())
    assert err < 2e-2, (arch_id, err)
    print(f"OK decode {arch_id} on mesh == local: {err:.2e}")
print("ALL_DISTRIBUTED_OK")
"""


@pytest.mark.slow
def test_sharded_steps_run_with_real_values_on_8_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", _SCRIPT], env=env, capture_output=True,
        text=True, timeout=540, cwd=os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
    assert "ALL_DISTRIBUTED_OK" in proc.stdout, (
        proc.stdout[-2000:], proc.stderr[-4000:])
