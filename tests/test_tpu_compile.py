"""Compile the main path's TAA kernels for a described TPU v5e chip.

The Pallas TPU compiler is installed with JAX and compiles for a chip that
is described rather than attached, so these tests catch what interpret
mode cannot (block shapes off the (8, 128) tiling, unsupported in-kernel
ops, VMEM overruns) without a chip.  Shapes are DiT-XL/2 at 256x256: T=25
rows of D = 256 tokens x 16 = 4096, history m=3, vmapped over 4 request
slots the way the sampling engine calls them.  Nothing runs; each test
only compiles (about a second each).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import taa_update

SLOTS, M, T, D = 4, 3, 25, 256 * 16


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # libtpu logs nowhere
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    text = jax.jit(jax.vmap(fn)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_taa_gram_compiles_for_v5e(one_chip):
    _compile(taa_update.taa_gram, one_chip,
             (SLOTS, M, T, D), (SLOTS, T, D), (SLOTS, T))


def test_taa_apply_compiles_for_v5e(one_chip):
    _compile(taa_update.taa_apply, one_chip,
             (SLOTS, T, D), (SLOTS, T, D), (SLOTS, M, T, D),
             (SLOTS, M, T, D), (SLOTS, T, M), (SLOTS, T))


@pytest.mark.parametrize("mode", ["taa", "aa", "aa+"])
def test_taa_round_compiles_for_v5e(one_chip, mode):
    def fused(x, R, dX, dF, mask, guard):
        return taa_update.taa_round(x, R, dX, dF, mask, guard, mode=mode)

    _compile(fused, one_chip,
             (SLOTS, T, D), (SLOTS, T, D), (SLOTS, M, T, D),
             (SLOTS, M, T, D), (SLOTS, T), (SLOTS, T))
