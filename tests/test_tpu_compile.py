"""Compile the main path's kernels and the served decode step for a TPU v5e.

Nothing runs: each case lowers and compiles for a *described* v5e chip, so
the TPU compiler refuses here what it would refuse on the device (unaligned
SMEM/VMEM blocks, kernels that do not fit VMEM, a step that does not fit
HBM).  The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and the suite runs on several
workers.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 15.75e9  # usable HBM of one v5e chip, as its compiler reports


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: entries
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), "kernel not compiled"


@pytest.mark.parametrize("T", [1024, 16384, 65536])
def test_lags_select_compiles(one_chip, T):
    from repro.kernels.lags_select import lags_select

    f = jax.jit(lambda l, c, fr, r: lags_select(l, c, fr, r, 16))
    args = [_sds(one_chip, (T,), jnp.float32) for _ in range(3)]
    args.append(_sds(one_chip, (T,), jnp.bool_))
    _assert_kernel(f.lower(*args).compile())


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention import flash_attention

    q = _sds(one_chip, (1, 32, 2048, 64), jnp.bfloat16)
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    _assert_kernel(f.lower(q, q, q).compile())


def test_decode_attention_compiles(one_chip):
    from repro.kernels.decode_attention import decode_attention

    B, H, L, D = 16, 32, 2048, 64
    q = _sds(one_chip, (B, H, D), jnp.bfloat16)
    kv = _sds(one_chip, (B, H, L, D), jnp.bfloat16)
    lens = _sds(one_chip, (B,), jnp.int32)
    f = jax.jit(decode_attention)
    _assert_kernel(f.lower(q, kv, kv, lens).compile())


def test_stablelm_decode_step_fits_one_chip(one_chip):
    """The engine's full-width decode step (16 slots x 1024 tokens) fits
    one v5e's HBM: arguments + outputs + temporaries - aliased bytes."""
    from repro.configs.base import get_config
    from repro.models import model
    from repro.models.params import spec_to_sds
    from repro.serving.engine import decode_and_pick

    cfg = get_config("stablelm-1.6b")
    assert cfg.dtype == "bfloat16" and cfg.n_layers == 24
    on_chip = lambda t: jax.tree_util.tree_map(
        lambda s: _sds(one_chip, s.shape, s.dtype), t)
    params = on_chip(spec_to_sds(model.abstract_params(cfg)))
    cache = on_chip(spec_to_sds(model.cache_specs(cfg, 16, 1024)))
    tokens = _sds(one_chip, (16, 1), jnp.int32)
    pos = _sds(one_chip, (), jnp.int32)
    step = decode_and_pick(cfg)
    m = step.lower(params, tokens, cache, pos).compile().memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= V5E_HBM_BYTES, (
        f"decode step needs {total / 1e9:.2f} GB of {V5E_HBM_BYTES / 1e9} GB")


def test_fleet_scan_has_no_gather_or_scatter(one_chip):
    """The vmapped fleet scan at the Fig 7 fleet's shape (10 nodes, 640
    slots, 58 requests a slot) compiles with no gather or scatter: the TPU
    runs one with per-node indices nearly an element at a time."""
    from repro.core import simkernel_jax as sj

    p = sj.SimParams(n_cores=12, n_fns=80, n_ticks=15000, policy=sj.LAGS,
                     burst_us=280.0, depth=5.0)
    trace = sj.SlotTrace(_sds(one_chip, (10, 640, 58), jnp.int32),
                         _sds(one_chip, (10, 640, 58), jnp.float32),
                         _sds(one_chip, (10, 640), jnp.int32))
    f = jax.jit(lambda t: jax.vmap(lambda x: sj.simulate(x, p))(t))
    hlo = f.lower(trace).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    assert any("/while/body/" in n for n in op_names)
    indexed = sorted({n for n in op_names if re.search("gather|scatter", n)})
    assert not indexed, indexed
