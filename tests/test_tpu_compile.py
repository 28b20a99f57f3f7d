"""Compile the main path's kernels for a described TPU v5e chip.

The Pallas TPU compiler is installed with JAX and compiles for a chip that
is described rather than attached, so these tests catch what interpret
mode cannot (block shapes off the (8, 128) tiling, unsupported in-kernel
ops, VMEM overruns) without a chip.  TAA shapes are DiT-XL/2 at 256x256: T=25
rows of D = 256 tokens x 16 = 4096, history m=3, vmapped over 4 request
slots the way the sampling engine calls them; the DiT attention kernel
runs at both benchmark cells' shapes, PixArt's cross-attention kernel at
its cell's.  Nothing runs; each test only
compiles (about a second a kernel, ten for a step program).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest worker
imports this file.
"""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention, taa_update

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


def _step_program(one_chip, cfg, solver):
    """The compiled HLO text of the engine's stepwise step program for
    ``cfg``'s denoiser, served as ``serve.make_engine`` serves it (the
    block weights per layer), compiled for the described chip."""
    from repro.core import ddim_coeffs
    from repro.launch import serve
    from repro.models.pdefs import is_def
    from repro.sampling import get_sampler
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.tree.map(lambda d: sds(d.shape, jnp.float32),
                          serve.denoiser(cfg).defs(cfg), is_leaf=is_def)
    engine = serve.make_engine(params, cfg, ddim_coeffs(8),
                               get_sampler(solver, use_pallas=True))
    xi = sds((9,) + tuple(engine.sample_shape), jnp.float32)
    state = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        engine._stepwise_program("open", SLOTS), xi))
    conds = jax.tree.map(
        lambda c: sds((SLOTS,) + np.shape(c), np.asarray(c).dtype),
        engine.condition(None))
    return engine._stepwise_program("step", 1).lower(
        engine.params, state, conds).compile().as_text()


def _step_program_kernels(one_chip, cfg, solver):
    """(instruction name, op_name) of every Pallas call in the engine's
    stepwise step program, compiled for the described chip."""
    return re.findall(r"(%\S+) = \S+ custom-call\([^)]*\), "
                      r'custom_call_target="tpu_custom_call".*?'
                      r'op_name="([^"]*)"',
                      _step_program(one_chip, cfg, solver))


def _names(kernels):
    return {re.sub(r"\.\d+$", "", k) for k, _ in kernels}


def test_step_program_names_the_taa_kernels(one_chip):
    """In the engine's stepwise step program the kernels' instructions are
    named after them (``%_taa_gram.N``, ``%_taa_apply.N``), which is what a
    device trace shows and what the benchmark's kernel share matches."""
    from repro.configs.registry import ARCHS
    kernels = _step_program_kernels(one_chip, ARCHS["dit-xl"].reduced(),
                                    "taa")
    assert kernels and all(k.startswith("%_taa_") for k, _ in kernels)
    assert _names(kernels) == {"%_taa_gram", "%_taa_apply"}


@pytest.mark.parametrize("rows,n", [(25, 256), (1, 1024)],
                         ids=["taa-256", "seq-1024"])
def test_dit_flash_compiles_for_v5e(one_chip, rows, n):
    """The DiT attention kernel at the benchmark cells' shapes: 8 slots of
    16 heads of 72 for a 25-row window at 256 tokens, and for one row at
    1024 tokens (q, k, v as (D, N))."""
    qkv = (SLOTS * 2, 16, rows, 72, n)
    _compile(flash_attention.dit_flash_attention, one_chip, "_dit_flash",
             qkv, qkv, qkv)


@pytest.mark.parametrize("solver", ["taa", "seq"])
def test_step_program_runs_dit_attention_in_the_kernel(one_chip, solver,
                                                       monkeypatch):
    """On a TPU every DiT layer's attention is one ``%_dit_flash.N`` call
    under the ``dit/attn`` scope (what ``attn_share`` reads), and the only
    ``%_taa_`` calls are the solver's.  Full depth, smoke widths."""
    import sys
    from pathlib import Path
    from repro.configs.registry import ARCHS
    from repro.kernels import ops
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from scopes import scope_path
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    layers = ARCHS["dit-xl"].num_layers
    cfg = dataclasses.replace(ARCHS["dit-xl"].reduced(), num_layers=layers)
    kernels = _step_program_kernels(one_chip, cfg, solver)
    flash = [op for k, op in kernels if k.startswith("%_dit_flash.")]
    assert len(flash) == layers
    assert all(scope_path(op).endswith("dit/attn") for op in flash)
    taa = {"taa": {"%_taa_gram", "%_taa_apply"}, "seq": set()}[solver]
    assert _names(kernels) == {"%_dit_flash"} | taa


def test_dit_xflash_compiles_for_v5e(one_chip):
    """PixArt's cross-attention kernel at its cell's shapes: 8 slots of 2
    guided rows, 16 heads of 72, 1024 queries against the caption's 120
    keys padded to 128, each row masked past its own count."""
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    args = (f32((8, 16, 2, 72, 1024)), f32((8, 16, 2, 72, 128)),
            f32((8, 16, 2, 72, 128)),
            jax.ShapeDtypeStruct((8, 2), jnp.int32, sharding=one_chip))
    lowered = jax.jit(jax.vmap(
        flash_attention.dit_cross_flash_attention)).lower(*args)
    assert 'kernel_name = "_dit_xflash"' in lowered.as_text()
    text = lowered.compile().as_text()
    assert re.search(r"%\S*_dit_xflash\S* = .*tpu_custom_call", text)


def test_pixart_step_program_runs_both_kernels(one_chip, monkeypatch):
    """In PixArt's seq step program every layer's self-attention is one
    ``%_dit_flash.N`` under ``dit/attn`` and its cross-attention one
    ``%_dit_xflash.N`` under ``dit/xattn`` (what ``xattn_share`` and
    ``xattn_roofline`` read).  Full depth, smoke widths."""
    import sys
    from pathlib import Path
    from repro.configs.registry import ARCHS
    from repro.kernels import ops
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from scopes import scope_path
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    layers = ARCHS["pixart-alpha"].num_layers
    cfg = dataclasses.replace(ARCHS["pixart-alpha"].reduced(),
                              num_layers=layers)
    kernels = _step_program_kernels(one_chip, cfg, "seq")
    scopes = {"%_dit_flash.": "dit/attn", "%_dit_xflash.": "dit/xattn"}
    for prefix, scope in scopes.items():
        ops_ = [op for k, op in kernels if k.startswith(prefix)]
        assert len(ops_) == layers, prefix
        assert all(scope_path(op).endswith(scope) for op in ops_)
    assert _names(kernels) == {"%_dit_flash", "%_dit_xflash"}


@pytest.mark.parametrize("arch", ["dit-xl", "pixart-alpha"])
def test_seq_step_copies_no_block_weight(one_chip, arch, monkeypatch):
    """At full widths and 1024 tokens, the served seq step has no fusion,
    copy or transpose whose output has the shape of a block weight: the
    dots read each layer's f32 weights as they are.  (A layer loop that
    slices stacked blocks writes 224 such outputs a DiT step, 2.08 GB,
    and 476 a PixArt step, 1.19 GB: bf16 copies for the MXU.)"""
    from repro.configs.registry import ARCHS
    from repro.kernels import ops
    from repro.launch import serve
    from repro.models.pdefs import is_def
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(ARCHS[arch], num_tokens=1024)
    text = _step_program(one_chip, cfg, "seq")
    blocks = serve.denoiser(cfg).defs(cfg)["blocks"]
    shapes = {",".join(map(str, d.shape[1:]))
              for d in jax.tree.leaves(blocks, is_leaf=is_def)
              if len(d.shape) > 2}
    outputs = re.findall(r"= (.*?) (?:fusion|copy|transpose)\(", text)
    assert len(outputs) > 500
    copies = [sh for out in outputs
              for sh in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", out)
              if sh in shapes]
    assert copies == []
