"""Compile the device programs for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached.  It refuses what interpret mode accepts: a
block not aligned to the tiling, a dynamic scalar read from VMEM, a
kernel that overruns scoped VMEM.  So each Pallas kernel is compiled here
at the kernel bench's full shapes, and the simulator's batched link tick
at the 512-tenant, 64-instance group of the ``--many`` top rung.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under several
test workers a call at import would make the workers collect different
tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import DySkewConfig, Policy, SkewModelKind
from repro.kernels.dispatch.kernel import dispatch_gather
from repro.kernels.histogram.kernel import load_histogram
from repro.kernels.ssd_scan.kernel import ssd_state_scan
from repro.kernels.topk_gating.kernel import topk_gating
from repro.sim.batched_link import _batched_tick_impl, _stacked_host_link_state


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


# name -> (call, [(shape, dtype), ...]) at benchmarks/bench_kernels.py's
# full sizes.
KERNELS = {
    "dispatch_gather": (
        lambda x, s, v: dispatch_gather(x, s, v),
        [((1024, 512), jnp.bfloat16), ((2048,), jnp.int32),
         ((2048,), jnp.bool_)],
    ),
    "load_histogram": (
        lambda ids: load_histogram(ids, num_dest=384),
        [((8192,), jnp.int32)],
    ),
    "topk_gating": (
        lambda logits: topk_gating(logits, k=8),
        [((1024, 384), jnp.float32)],
    ),
    "ssd_state_scan": (
        lambda s, d: ssd_state_scan(s, d),
        [((32, 16, 64, 128), jnp.float32), ((32, 16), jnp.float32)],
    ),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = KERNELS[name]
    shapes = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_batched_tick_compiles_for_v5e(one_chip):
    """`BatchedLinkSim`'s jitted tick at T=512 tenants x n=64 instances
    (8 nodes x 8 interpreters), with the --many study's config."""
    cfg = DySkewConfig(
        policy=Policy.LATE, skew_model=SkewModelKind.IDLE_TIME, n_strikes=2
    )
    T, n = 512, 64
    state = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        _stacked_host_link_state(T, n, cfg),
    )
    f32 = jax.ShapeDtypeStruct((T, n), jnp.float32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((T, n), jnp.bool_, sharding=one_chip)
    active = jax.ShapeDtypeStruct((T,), jnp.bool_, sharding=one_chip)
    compiled = _compile(
        lambda *a: _batched_tick_impl(*a, cfg=cfg),
        state, f32, f32, f32, f32, mask, active,
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.argument_size_in_bytes < 16 * 2**30
