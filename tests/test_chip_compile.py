"""Compile the main path's device programs for a described TPU v5e chip.

Nothing runs: the TPU compiler, which is installed even where no chip is
attached, lowers each program for a chip of a described ``v5e:2x2``
topology and refuses what the chip would refuse — block shapes off the
(8, 128) tiling, layouts Mosaic cannot lower, programs that overflow device
memory.  Interpret-mode tests cannot see any of that.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import steps as ST
from repro.configs import get_config
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_chunk import gla_chunk, gla_chunk_parallel
from repro.launch.mesh import make_host_mesh
from repro.models import Model
from repro.optim import make_optimizer, wsd
from repro.sharding import ShardingCtx, rules_for

#: one v5e chip's HBM (Google Cloud, "TPU v5e": 16 GB per chip)
V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def v5e_2x2():
    """A described v5e:2x2 topology; the persistent compile cache is off
    meanwhile (entries compiled for a described chip cannot be read back
    without one, and the next compile would warn)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    """One chip of the described topology."""
    return SingleDeviceSharding(v5e_2x2.devices[0])


def _granite_kernel_args():
    cfg = get_config("granite-3-2b")
    return cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim


def _mlstm_kernel_args():
    """xlstm-350m's mLSTM heads as models/xlstm.py forms them: the block's
    inner width d * m_proj_factor split over n_heads, chunk from the
    config."""
    cfg = get_config("xlstm-350m")
    x = cfg.xlstm
    n = int(cfg.d_model * x.m_proj_factor) // x.n_heads
    return x.n_heads, n, x.chunk


def _kernel_case(name):
    """(fn, [(shape, dtype), ...]) at real widths for one kernel."""
    H, K, D = _granite_kernel_args()
    bf, i32, f32 = jnp.bfloat16, jnp.int32, jnp.float32
    if name == "flash_attention":
        S = 2048
        return (functools.partial(flash_attention, causal=True),
                [((1, H, S, D), bf), ((1, K, S, D), bf), ((1, K, S, D), bf)])
    if name == "decode_attention":
        B, S = 4, 1024
        return (functools.partial(decode_attention, n_splits=8),
                [((B, H, D), bf), ((B, S, K, D), bf), ((B, S, K, D), bf),
                 ((), i32)])
    if name == "paged_decode_attention":
        B, pool, page, n_pages = 4, 256, 16, 64
        return (paged_decode_attention,
                [((B, H, D), bf), ((pool, page, K, D), bf),
                 ((pool, page, K, D), bf), ((B, n_pages), i32), ((B,), i32)])
    Hm, N, chunk = _mlstm_kernel_args()
    S = 2048
    fn = gla_chunk if name == "gla_chunk" else gla_chunk_parallel
    return (functools.partial(fn, chunk=chunk),
            [((1, S, Hm, N), bf), ((1, S, Hm, N), bf), ((1, S, Hm, N), bf),
             ((1, S, Hm), f32)])


@pytest.mark.parametrize("name", ["flash_attention", "decode_attention",
                                  "paged_decode_attention", "gla_chunk",
                                  "gla_chunk_parallel"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Mosaic kernel in the compiled program"


def test_granite_train_step_fits_one_v5e(one_chip):
    """Two granite-3-2b layers at published widths, batch 4 x 2048, AdamW
    with f32 moments and donated state: compiles and fits one chip."""
    cfg = dataclasses.replace(get_config("granite-3-2b"), n_layers=2)
    total = _step_bytes(cfg, one_chip)
    assert total < V5E_HBM_BYTES, f"needs {total / 1e9:.2f} GB"


def _step_bytes(cfg, one_chip):
    """Device bytes of one AdamW train step of ``cfg`` at batch 4 x 2048,
    compiled for one chip, with its state donated."""
    model = Model(cfg)
    ctx = ShardingCtx(None, rules_for(cfg, "train"))
    opt = make_optimizer(cfg, wsd(3e-3, 50, 1000))

    def placed(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)

    params = model.abstract()
    tokens = jax.ShapeDtypeStruct((4, 2048), jnp.int32, sharding=one_chip)
    step = jax.jit(ST.make_train_step(model, ctx, opt),
                   donate_argnums=(0, 1))
    compiled = step.lower(placed(params),
                          placed(jax.eval_shape(opt.init, params)),
                          {"tokens": tokens, "targets": tokens},
                          jax.ShapeDtypeStruct((), jnp.int32,
                                               sharding=one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes > 0, "state was not donated"
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)


def test_hybrid_train_step_fits_one_v5e(one_chip):
    """A Mamba-2 layer and a NoPE attention layer of granite-4.0-h-micro,
    half of each as the benchmark's cell holds it (32 of 64 Mamba-2 heads,
    16/4 of 32/8 attention heads, 4096 of 8192 MLP columns, 50,176 of the
    tied vocabulary): the chunked scan at d_state 128 compiles and fits."""
    base = get_config("granite-4.0-h-micro")
    cfg = dataclasses.replace(
        base, n_layers=2, layer_types=("mamba", "attention"), n_heads=16,
        n_kv_heads=4, d_ff=4096, vocab_size=50176,
        ssm=dataclasses.replace(base.ssm, n_ssm_heads=32))
    total = _step_bytes(cfg, one_chip)
    assert total < V5E_HBM_BYTES, f"needs {total / 1e9:.2f} GB"


def test_host_mesh_on_v5e_2x2(v5e_2x2):
    """Over a host's four chips the 'model' axis is a physical ring (each
    step one hop on the 2x2 grid, wrap included); over two of them the
    mesh holds exactly those two (the elastic-restart target)."""
    devs = v5e_2x2.devices
    ring = list(make_host_mesh(devices=devs).devices.flat)
    assert sorted(d.id for d in ring) == sorted(d.id for d in devs)
    for a, b in zip(ring, ring[1:] + ring[:1]):
        assert sum(abs(x - y) for x, y in zip(a.coords, b.coords)) == 1, \
            [d.coords for d in ring]
    two = make_host_mesh(devices=devs[:2])
    assert list(two.devices.flat) == list(devs[:2])
    assert dict(two.shape) == {"data": 1, "model": 2}
