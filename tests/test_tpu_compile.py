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
import re

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


def _compile(fn, one_chip, kernel, *shapes):
    """Compile ``fn`` vmapped over the slots; its Pallas call carries the
    name a profiler shows for the kernel."""
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    lowered = jax.jit(jax.vmap(fn)).lower(*args)
    assert f'kernel_name = "{kernel}"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert re.search(rf"%\S*{kernel}\S* = .*tpu_custom_call", text)


def test_taa_gram_compiles_for_v5e(one_chip):
    _compile(taa_update.taa_gram, one_chip, "_taa_gram",
             (SLOTS, M, T, D), (SLOTS, T, D), (SLOTS, T))


def test_taa_apply_compiles_for_v5e(one_chip):
    _compile(taa_update.taa_apply, one_chip, "_taa_apply",
             (SLOTS, T, D), (SLOTS, T, D), (SLOTS, M, T, D),
             (SLOTS, M, T, D), (SLOTS, T, M), (SLOTS, T))


@pytest.mark.parametrize("mode", ["taa", "aa", "aa+"])
def test_taa_round_compiles_for_v5e(one_chip, mode):
    def fused(x, R, dX, dF, mask, guard):
        return taa_update.taa_round(x, R, dX, dF, mask, guard, mode=mode)

    _compile(fused, one_chip, "_taa_round",
             (SLOTS, T, D), (SLOTS, T, D), (SLOTS, M, T, D),
             (SLOTS, M, T, D), (SLOTS, T), (SLOTS, T))


def test_step_program_names_the_taa_kernels(one_chip):
    """In the engine's stepwise step program the kernels' instructions are
    named after them (``%_taa_gram.N``, ``%_taa_apply.N``), which is what a
    device trace shows and what the benchmark's kernel share matches."""
    from repro.configs.registry import ARCHS
    from repro.core import ddim_coeffs
    from repro.diffusion import dit
    from repro.launch import serve
    from repro.models.pdefs import is_def
    from repro.sampling import get_sampler
    cfg = ARCHS["dit-xl"].reduced()
    params = jax.tree.map(
        lambda d: jax.ShapeDtypeStruct(d.shape, jnp.float32,
                                       sharding=one_chip),
        dit.dit_defs(cfg), is_leaf=is_def)
    engine = serve.make_engine(params, cfg, ddim_coeffs(8),
                               get_sampler("taa", use_pallas=True))
    xi = jax.ShapeDtypeStruct((9,) + tuple(engine.sample_shape),
                              jnp.float32)
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(engine._stepwise_program("open", SLOTS), xi))
    labels = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    text = engine._stepwise_program("step", 1).lower(
        params, state, labels).compile().as_text()
    kernels = re.findall(r"(%\S+) = \S+ custom-call\([^)]*\), "
                         r'custom_call_target="tpu_custom_call"', text)
    assert kernels and all(k.startswith("%_taa_") for k in kernels)
    assert {re.sub(r"\.\d+$", "", k) for k in kernels} == \
        {"%_taa_gram", "%_taa_apply"}

