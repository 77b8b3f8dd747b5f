"""Ahead-of-time compiles of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached, so each test
compiles one kernel at full ResNet-Tiny width — k = 10 cohort rows of the
padded P = 4,698,112 — for a described, unattached v5e chip, and asserts
that the compiled program holds the Mosaic kernel (``tpu_custom_call``).
This catches what interpret mode cannot: unsupported ops (unsigned
reductions) and blocks that overflow scoped VMEM at this width.  Nothing
runs, so results and times are not checked here; ``chip_smoke.py`` does
that on the chip.

The topology is described inside a module-scoped fixture, never at import:
only one process may hold the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

K, P_PADDED, P = 10, 4_698_112, 4_696_394  # ResNet-Tiny (64/128/256, 4/4/3)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler here: nothing to check against
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # an AOT compile is written to the persistent cache but cannot be
        # read back without a chip, so keep the cache off for these tests
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _rows(dtype, sharding, shape=(K, P_PADDED)):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


KERNELS = {
    "masked_agg": lambda s: ops.masked_aggregate.lower(
        _rows(jnp.uint32, s), _rows(jnp.uint32, s), 10.0, 20, interpret=False),
    "compress": lambda s: ops.clip_quant_mask.lower(
        _rows(jnp.float32, s), _rows(jnp.uint32, s), 1.0, 20, dim=P, interpret=False),
    "staleness_agg": lambda s: ops.staleness_aggregate.lower(
        _rows(jnp.float32, s), _rows(jnp.float32, s, (K,)), interpret=False),
    "gossip_mix": lambda s: ops.gossip_mix.lower(
        _rows(jnp.float32, s), _rows(jnp.float32, s, (K, K)), interpret=False),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e_at_resnet_tiny_width(name, one_chip):
    compiled = KERNELS[name](one_chip).compile()  # raises what the chip's compiler would
    assert "tpu_custom_call" in compiled.as_text()


def test_enable_compile_cache_keeps_one_fixed_path(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes to
    <checkout>/.jax_cache.  jax.config.update is recorded, not applied, so
    the test process never turns the cache on."""
    from pathlib import Path

    from repro.utils import enable_compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update", lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/set/by/the/host")
    enable_compile_cache()
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    enable_compile_cache()
    checkout = Path(__file__).resolve().parents[1]
    assert calls == [("jax_compilation_cache_dir", str(checkout / ".jax_cache"))]
