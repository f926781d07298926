"""Compile checks for a TPU v5e, made without a chip.

The TPU compiler compiles for a described `v5e:2x2` topology: what it
refuses here (unaligned tiles, too much fast memory, a program that does
not fit the device) would fail on the chip.  Nothing runs, so these say
nothing about results or times.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file.  Keep these
tests in this one file, so that one worker loads the library.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.paged_attention.kernel import paged_attention_pallas
from repro.kernels.rma.kernel import put_shift_pallas
from repro.models import build_model

HBM_BYTES = 16 * 10**9        # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding), tree)


def test_smollm_decode_step_fits_one_chip(one_chip):
    """Full-width SmolLM-360M decode step at the serving shape of
    `chip_smoke.py` (4 slots, max_seq 512), compiled plain and with the
    cache donated as `ServeEngine` compiles it: the donated program updates
    the cache in place, so it aliases every cache byte and needs less."""
    model = build_model(get_config("smollm-360m"))
    params = _on(one_chip, model.init_shapes())
    cache = _on(one_chip, jax.eval_shape(lambda: model.init_cache(4, 512)))
    token = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    cache_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))

    def compiled_memory(**jit_kw):
        compiled = jax.jit(model.decode_step, **jit_kw).lower(params, token, cache).compile()
        ma = compiled.memory_analysis()
        used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        return used, ma.alias_size_in_bytes

    used, _ = compiled_memory()
    assert 0 < used < HBM_BYTES, used
    used_donated, alias = compiled_memory(donate_argnames="cache")
    assert alias >= cache_bytes, (alias, cache_bytes)
    assert used_donated < used, (used_donated, used)


def test_moonlight_decode_step_fits_one_chip(one_chip):
    """Full-width Moonlight-16B-A3B decode step at the benchmark's serving
    shape (16 of 64 experts held, 128 slots of 1024 latent positions),
    compiled with the cache donated as `ServeEngine` compiles it: weights and
    latent cache fit one chip, the cache is updated in place, and the held
    experts run in the compiler's grouped matmul (the ragged-dot kernel),
    not in a matmul of every token against every expert."""
    model = build_model(dataclasses.replace(get_config("moonlight-16b-a3b"), moe_held=16))
    params = _on(one_chip, model.init_shapes())
    cache = _on(one_chip, jax.eval_shape(lambda: model.init_cache(128, 1024)))
    token = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one_chip)
    lat_bytes = cache["kv"]["lat"].size * 2
    assert lat_bytes == 128 * 1024 * 27 * 576 * 2          # 31,104 B a position
    compiled = jax.jit(model.decode_step, donate_argnames="cache").lower(
        params, token, cache).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert ma.alias_size_in_bytes >= lat_bytes
    assert used < HBM_BYTES, used
    text = compiled.as_text()
    assert "ragged-dot" in text and "tpu_custom_call" in text


@pytest.mark.parametrize("pt,hd,n_pages,m,k", [
    (4, 32, 48, 4, 4),        # the disaggregated engine's pages
    (16, 128, 64, 8, 8),
])
def test_paged_attention_compiles(one_chip, pt, hd, n_pages, m, k):
    q = jax.ShapeDtypeStruct((m, 1, hd), jnp.float32, sharding=one_chip)
    pages = jax.ShapeDtypeStruct((n_pages, pt, 2, hd), jnp.float32,
                                 sharding=one_chip)
    ids = jax.ShapeDtypeStruct((m, k), jnp.int32, sharding=one_chip)
    fn = functools.partial(paged_attention_pallas, scale=1.0, causal=False,
                           interpret=False)
    text = jax.jit(fn).lower(q, pages, ids).compile().as_text()
    assert "tpu_custom_call" in text


def test_put_shift_compiles_over_four_chips(topo):
    """The compiled ring put the plan picks on a TPU for >= ~300 KB
    (8,128)-tileable payloads, in shard_map over the four chips."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("x",))
    spec = P("x", None)
    x = jax.ShapeDtypeStruct((4 * 1024, 256), jnp.float32,
                             sharding=NamedSharding(mesh, spec))
    put = functools.partial(put_shift_pallas, shift=1, axis="x", n=4,
                            interpret=False)
    fn = jax.jit(shard_map(put, mesh=mesh, in_specs=spec, out_specs=spec,
                           check_vma=False))
    assert "tpu_custom_call" in fn.lower(x).compile().as_text()
