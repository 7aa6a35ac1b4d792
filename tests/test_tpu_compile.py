"""Mosaic compiles of the fused OTA kernel for a TPU v5e, without a chip.

The TPU compiler compiles for a described (not attached) `v5e:2x2`
topology, so these tests catch what interpret mode cannot — block
shapes off the (8, 128) tiling, casts Mosaic lacks, VMEM overruns — at
the main path's real shapes.  The kernel is compiled directly
(``interpret=False``): `repro.kernels.interpret_mode` sees the CPU here.
The topology is described inside a fixture, never at import, and the
tests skip where it cannot be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import canonical_block_u, fused_mac, fused_mac_partials

N_MNIST = 3925        # complex symbols of the 7,850-parameter MNIST MLP
N_CIFAR = 154197      # complex symbols of the 308,394-parameter CIFAR CNN


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _compile(kernel, sharding, B, U, K, N, **kw):
    def sds(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    args = (sds((2,), jnp.uint32), sds((U, N)), sds((U, N)), sds((B, U)),
            sds((B, U)))
    fn = jax.jit(lambda *a: kernel(*a, K=K, sigma_h2=1.0, interpret=False,
                                   **kw))
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("name,B,U,K,M", [
    ("fig2_faithful", 4, 20, 100, 5),       # C=4 ISs hear C*M=20 users
    ("scale_u1024", 8, 1024, 16, 128),
    ("fig2_ps_hop", 1, 4, 100, None),       # the PS hears C=4 ISs
])
def test_fused_mac_compiles_for_v5e(one_chip, name, B, U, K, M):
    """M=None: the default u-block, as the IS->PS hop calls the kernel."""
    blocking = {} if M is None else dict(block_u=canonical_block_u(M))
    text = _compile(fused_mac, one_chip, B, U, K, N_MNIST, sigma_z2=1.0,
                    **blocking)
    assert "tpu_custom_call" in text, name


@pytest.mark.parametrize("name,B,U,M", [
    ("fig3_faithful", 4, 20, 5),            # 302 symbol blocks of 512
    ("fig3_ps_hop", 1, 4, None),
])
def test_fused_mac_compiles_for_v5e_at_cifar_width(one_chip, name, B, U, M):
    """The CNN's hops (K = K_ps = 100), at the paper's largest N."""
    blocking = {} if M is None else dict(block_u=canonical_block_u(M))
    text = _compile(fused_mac, one_chip, B, U, 100, N_CIFAR, sigma_z2=1.0,
                    **blocking)
    assert "tpu_custom_call" in text, name


def test_fused_mac_partials_compiles_for_v5e(one_chip):
    """One u-tile of scale_u16384 (C=16, M=1024, K=4) on a 2x2 mesh:
    all 16 rx stations, the tile's 8 clusters' users, half the
    symbols."""
    text = _compile(fused_mac_partials, one_chip, 16, 8 * 1024, 4,
                    -(-N_MNIST // 2), block_u=canonical_block_u(1024))
    assert "tpu_custom_call" in text
