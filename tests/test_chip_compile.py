"""Compile the served path for a described TPU v5e chip, without a chip.

The TPU compiler is installed with JAX, so it can compile for a chip that
is described and not attached: what it refuses here (tiles that break the
TPU's layout rules, kernels that need more VMEM than a core has, programs
that do not fit the chip's memory) would be refused on the chip.  Nothing
runs, so these cases say nothing about results or times.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core import jobs
from repro.kernels import ops
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssd_scan import ssd_pallas
from repro.models import lm

#: v5e has 16 GB of HBM per chip.
V5E_HBM_BYTES = 16 * 10**9
#: The served configuration of chip_smoke.py: yi-6b at published widths,
#: 8 of its 32 layers; batch 8, prompt 512, a cache for 32 blocks of 8.
YI_8_LAYERS = dict(layers=8, batch=8, prompt_len=512, max_seq=512 + 256 + 8)
#: The served cut of the chip benchmark's deepseek-v2-lite-5L: the dense
#: layer and 4 expert layers at published widths; batch 8, prompt 512, a
#: cache for 16 blocks of 8.
DSV2_5_LAYERS = dict(layers=5, batch=8, prompt_len=512,
                     max_seq=512 + 128 + 8)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compile_v5e(one_chip):
    """``compile_v5e(fn, *structs)`` lowers and compiles ``fn`` for one v5e
    chip; the persistent compile cache is off around it (a compile for a
    described chip cannot be read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()

    def place(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), tree)

    def compile_(fn, *structs):
        return jax.jit(fn).lower(*place(structs)).compile()

    try:
        yield compile_
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


# Each kernel at the widths of the configuration it serves.
KERNELS = {
    # yi-6b prefill: one 2048-token sequence, 32 heads over 4 KV heads
    "flash_attention": (
        lambda q, k, v: flash_attention_pallas(q, k, v),
        (_sds((1, 2048, 32, 128)), _sds((1, 2048, 4, 128)),
         _sds((1, 2048, 4, 128)))),
    # yi-6b decode: batch 8 against a 2048-slot cache
    "decode_attention": (
        lambda q, k, v, n: decode_attention_pallas(q, k, v, n),
        (_sds((8, 32, 128)), _sds((8, 2048, 4, 128)),
         _sds((8, 2048, 4, 128)), _sds((8,), jnp.int32))),
    # mamba2-2.7b: 80 heads of P=64, N=128, chunk 128
    "ssd": (
        lambda x, dt, a, b, c: ssd_pallas(x, dt, a, b, c, chunk=128),
        (_sds((1, 2048, 80, 64)), _sds((1, 2048, 80), jnp.float32),
         _sds((80,), jnp.float32), _sds((1, 2048, 1, 128)),
         _sds((1, 2048, 1, 128)))),
    # recurrentgemma-2b: RG-LRU width 2560
    "rglru": (
        lambda x, a, i, la: rglru_pallas(x, a, i, la),
        (_sds((1, 2048, 2560)), _sds((1, 2048, 2560)),
         _sds((1, 2048, 2560)), _sds((2560,), jnp.float32))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_pallas_kernel_compiles_for_v5e(compile_v5e, name):
    fn, structs = KERNELS[name]
    compiled = compile_v5e(fn, *structs)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [48, 24576])
def test_grouped_product_compiles_for_v5e(compile_v5e, monkeypatch, rows):
    """The grouped product as ``ops.grouped_product`` runs it on a TPU
    (megablox ``gmm``) at DeepSeek-V2-Lite's expert widths: the rows of a
    decode step and of a prefill at batch 8, top-6, against a stack of four
    layers' 64 experts of 2048 x 1408."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = compile_v5e(ops.grouped_product, _sds((rows, 2048)),
                           _sds((4 * 64, 2048, 1408)),
                           _sds((4 * 64,), jnp.int32))
    assert "tpu_custom_call" in compiled.as_text()


def _served_structs(layers, batch, prompt_len, max_seq, arch="yi-6b"):
    import dataclasses
    cfg = dataclasses.replace(get_arch(arch), n_layers=layers)
    params = jax.eval_shape(
        lambda: jobs.serve_weights(cfg, jax.random.PRNGKey(0)))
    tokens = _sds((batch, prompt_len), jnp.int32)
    _, caches = jax.eval_shape(
        lambda p, t: lm.prefill(cfg, p, t, max_seq=max_seq), params, tokens)
    return cfg, params, tokens, caches


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_served_yi6b_step_fits_one_v5e(compile_v5e, step):
    """The served steps of chip_smoke.py's yi-6b cut compile for one chip,
    and their arguments plus temporaries fit its memory."""
    shape = dict(YI_8_LAYERS)
    cfg, params, tokens, caches = _served_structs(**shape)
    if step == "prefill":
        compiled = compile_v5e(
            lambda p, t: jobs._prefill(cfg, p, t, max_seq=shape["max_seq"]),
            params, tokens)
    else:
        vec = _sds((shape["batch"],), jnp.int32)
        compiled = compile_v5e(
            lambda p, t, c, n: jobs._decode(cfg, p, t, c, n),
            params, vec, caches, vec)
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (step, used)


@pytest.mark.parametrize("step", ["prefill", "decode"])
def test_served_deepseek_step_fits_one_v5e(compile_v5e, monkeypatch, step):
    """The served steps of the benchmark's DeepSeek-V2-Lite cut, with the
    grouped products a TPU runs, compile for one chip and fit its memory;
    the decode step copies no layer's experts out of their stack."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shape = dict(DSV2_5_LAYERS)
    cfg, params, tokens, caches = _served_structs(
        **shape, arch="deepseek-v2-lite-16b")
    if step == "prefill":
        compiled = compile_v5e(
            lambda p, t: jobs._prefill(cfg, p, t, max_seq=shape["max_seq"]),
            params, tokens)
    else:
        vec = _sds((shape["batch"],), jnp.int32)
        compiled = compile_v5e(
            lambda p, t, c, n: jobs._decode(cfg, p, t, c, n),
            params, vec, caches, vec)
        assert "bf16[64,2048,1408]" not in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < V5E_HBM_BYTES, (step, used)


def test_deepseek_decode_on_a_v5e_mesh_compiles(topo, monkeypatch):
    """DeepSeek-V2-Lite's decode step at published widths (the dense layer
    and one expert layer) on a 2x2 v5e mesh: the expert layer takes the
    per-row capacity dispatch there, which the compiler partitions, and no
    grouped-product kernel, which it cannot."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs.shapes import InputShape
    from repro.launch.steps import build_decode_step

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get_arch("deepseek-v2-lite-16b"), n_layers=2)
    bundle = build_decode_step(cfg, InputShape("d", 640, 8, "decode"),
                               mesh=mesh)
    compiled = bundle.fn.lower(*bundle.arg_specs).compile()
    assert "tpu_custom_call" not in compiled.as_text()
