"""The harness takes a model as new files: its generic modules name no
model, a second denoiser runs whole from a root of its own, and a cell's
traffic file can place the engine on a mesh."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

import harness  # noqa: E402

GENERIC = ("harness.py", "loadgen.py", "reference.py", "weights.py",
           "counts.py")
MODEL_WORDS = ("dit_", "label", "num_classes", "repro.diffusion")


@pytest.mark.parametrize("name", GENERIC)
def test_generic_module_names_no_model(name):
    """What depends on the model lives in ``models/<arch>.py``."""
    text = (harness.BENCH_DIR / name).read_text()
    assert [w for w in MODEL_WORDS if w in text] == []


# --- a second model, from a root of its own ---------------------------------

STUB = '''"""A one-layer denoiser on a token-mixing matrix, conditioned on a
"tone" drawn from a wide range and embedded by sinusoids (no table)."""
import jax
import jax.numpy as jnp


def program_arch(cfg):
    return {k: cfg[k] for k in ("num_tokens", "latent_dim", "d_model")}


def leaf_shapes(cfg):
    n, lat, d = cfg["num_tokens"], cfg["latent_dim"], cfg["d_model"]
    return {"mix": (n, n), "w_in": (lat, d), "w_out": (d, lat)}


def program_layout(arch):
    return leaf_shapes(arch)


def _eps(p, x, t, tone, prec):
    d = p["w_in"].shape[1]
    f = jnp.exp(-jnp.log(1e4) * jnp.arange(d // 2) / (d // 2))
    c = jnp.concatenate([jnp.sin(tone[:, None] * f),
                         jnp.cos(1e-3 * t[:, None] * f)], axis=-1)
    h = jnp.einsum("rnl,ld->rnd", x, p["w_in"], precision=prec)
    h = jnp.einsum("mn,rnd->rmd", p["mix"], h, precision=prec)
    return jnp.einsum("rnd,dl->rnl", jnp.tanh(h + c[:, None, :]),
                      p["w_out"], precision=prec)


def make_engine(params, arch, coeffs, spec, placement):
    from repro.sampling import SamplingEngine

    def eps_apply(p, x, taus, labels):
        return _eps(p, x, taus, labels.astype(jnp.float32), None)
    return SamplingEngine(eps_apply, params, coeffs, spec,
                          sample_shape=(arch["num_tokens"],
                                        arch["latent_dim"]),
                          placement=placement)


def draw_condition(rng, cfg):
    return {"tone": int(rng.integers(cfg["tones"]))}


def warm_condition(i, cfg):
    return {"tone": i}


def request_kwargs(cond):
    return {"label": cond["tone"]}


def forward(params, x, t, cond, *, dtype=jnp.float32):
    prec = "highest" if dtype == jnp.float32 else "default"
    p = jax.tree.map(lambda w: w.astype(dtype), params)
    tone = jnp.broadcast_to(jnp.asarray(cond["tone"], jnp.float32),
                            x.shape[:1])
    return _eps(p, x.astype(dtype), t.astype(jnp.float32), tone,
                prec).astype(jnp.float32)


def forward_flops(cfg):
    n, lat, d = cfg["num_tokens"], cfg["latent_dim"], cfg["d_model"]
    return 2.0 * (2 * n * lat * d + n * n * d)
'''

STUB_CONFIG = {
    "name": "stub-1", "arch": "stub", "num_tokens": 16, "latent_dim": 16,
    "d_model": 32, "tones": 100000,
    "init": {"mix": "fan_in_2", "w_in": "fan_in_2",
             "w_out": [1.0, "sqrt_fan_in"]}}
STUB_TRAFFIC = {
    "arrivals": "poisson", "arrival_seed": 1, "rate_per_s": 8.0,
    "sampler": "ddim", "T": 8,
    "solver": {"name": "taa", "order_k": 8, "history_m": 3, "window": 0,
               "fuse_round": False, "tau": 0.001},
    "slots": 4, "chunk_iters": 1, "max_wait_ms": 50, "drain_s": 3.0,
    "check": {"requests": 3, "block": 4}}
STUB_BENCHMARK = {
    "command": ["python3", "bench/run.py"], "paths": ["bench"],
    "run_seconds": 10,
    "configs": [{"name": "stub-1", "source": "none",
                 "file": "bench/configs/stub-1.json", "reduced": [],
                 "why": "a second denoiser"}],
    "workloads": [{"name": "stub-taa", "config": "stub-1",
                   "traffic": "stub-taa", "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"},
        {"name": "latency_p50_s", "unit": "s", "better": "lower",
         "bound": 0.04, "source": "host_clock"}],
    "per_layer": [
        {"name": "lane_useful_frac.stub", "unit": "ratio",
         "better": "higher", "source": "program_counter",
         "layer": "engine", "moves": "latency_p50_s",
         "workloads": ["stub-taa"]}]}


def write_stub_root(root: Path) -> None:
    """Every file the second model adds, and nothing of the DiT's."""
    files = {
        "BENCHMARK.json": json.dumps(STUB_BENCHMARK),
        "bench/configs/stub-1.json": json.dumps(STUB_CONFIG),
        "bench/models/stub.py": STUB,
        "bench/traffic/stub-taa.json": json.dumps(STUB_TRAFFIC),
        "bench/limits/stub-taa.json": json.dumps(
            {"step_gap": 0.015, "noise_row": 0.0, "unserved": 0.0}),
        "bench/metrics/lane_useful_frac.stub.py":
            "from layer_reads import lane_useful_frac as read  # noqa\n",
    }
    for rel, text in files.items():
        (root / rel).parent.mkdir(parents=True, exist_ok=True)
        (root / rel).write_text(text)


@pytest.mark.parametrize("fault,trace", [(None, False), (None, True),
                                         ("frozen", False)])
def test_second_model_runs_from_its_own_root(tmp_path, fault, trace):
    """A sound run of the stub is correct (traced, its own metric reader
    reads); a step that returns its state unchanged is not."""
    import contextlib
    import jax
    import faults
    write_stub_root(tmp_path)
    cell = harness.load_cell("stub-taa", root=tmp_path)
    assert cell.bench == tmp_path / "bench"
    with faults.planted(fault) if fault else contextlib.nullcontext():
        result = harness.run(cell, 2**31 + 7, 1.5, trace, time.monotonic(),
                             devices=jax.devices(), out=lambda *_: None)
    assert result["correct"] is (fault is None)
    if fault is None:
        assert result["attempted"] > 0 and result["failed"] == 0
        assert set(result["metrics"]) == (
            {"lane_useful_frac.stub"} if trace
            else {"setup_s", "latency_p50_s"})


# --- a mesh from the traffic file (subprocess, 4 host devices) ---------------

MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys, time
sys.path.insert(0, "bench")
import jax
import harness

SMALL = dict(num_layers=2, d_model=64, num_heads=4, head_dim=16, d_ff=128,
             latent_dim=16, num_tokens=16, num_classes=10)
cell = harness.load_cell("xl256-taa-poisson")
cell.config = dict(cell.config, **SMALL)
cell.traffic = dict(cell.traffic, drain_s=3.0, rate_per_s=8.0, T=8,
                    check={"requests": 3, "block": 4},
                    mesh={"name": "debug-time", "data": 1, "time": 4,
                          "model": 1})
cell.chips = 4
out = {}
try:
    harness.placement_for(cell.traffic, 2)
except SystemExit as e:
    out["refused"] = str(e)
out["host"] = harness.placement_for({}, 1).mesh is None

real = harness.build_system
def spy(cell, seed):
    system = real(cell, seed)
    out["mesh"] = dict(system.engine.placement.mesh.shape)
    return system
harness.build_system = spy
result = harness.run(cell, 2**31 + 5, 1.5, False, time.monotonic(),
                     devices=jax.devices(), out=lambda *_: None)
out.update(correct=result["correct"], attempted=result["attempted"],
           failed=result["failed"], count=result["device"]["count"])
print("RESULT " + json.dumps(out))
"""


def test_time4_mesh_cell_is_correct():
    """A ``time=4`` mesh named in the traffic file places the engine on
    four devices, and the run is correct; a mesh that does not span the
    cell's chips is refused, and no ``mesh`` key is the host placement."""
    proc = subprocess.run(
        [sys.executable, "-c", MESH_SCRIPT], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/tmp"), "JAX_PLATFORMS": "cpu"},
        cwd=harness.ROOT, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    out = json.loads(line[len("RESULT "):])
    assert out["mesh"] == {"data": 1, "time": 4, "model": 1}
    assert out["count"] == 4
    assert out["correct"] is True
    assert out["attempted"] > 0 and out["failed"] == 0
    assert "spans 4 devices" in out["refused"]
    assert out["host"] is True
