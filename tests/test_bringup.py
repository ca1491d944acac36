"""What chip bring-up promises, as far as a CPU can check it: the smoke
refuses to pass without a chip, the compile cache sits where it is told,
the Pallas kernels lower for TPU on one device AND under a mesh, host-only
roles cannot take the chip, and nothing reports a device rate off-device."""
import hashlib
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.utils import backend
from tpu_aot_rows import REPO as _REPO, run as _run, tpu_aot as _tpu_aot

_SMOKE = os.path.join(_REPO, "chip_smoke.py")


# ------------------------------------------------------------ chip_smoke.py


def test_chip_smoke_fails_without_a_chip_and_names_the_platform():
    out = _run([_SMOKE], env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "platform: cpu" in out.stdout
    assert "no accelerator" in out.stdout
    assert '"ok"' not in out.stdout  # never a verdict


def test_chip_smoke_fails_outside_a_checkout(tmp_path):
    shutil.copy(_SMOKE, tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], cwd=tmp_path,
               env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=""))
    assert out.returncode != 0
    assert "not at the root of a dedloc_tpu checkout" in out.stdout
    assert '"ok"' not in out.stdout


# ------------------------------------------------------------ compile cache

_PRINT_CACHE = (
    "import jax; from dedloc_tpu.utils.backend import ensure_compile_cache;"
    "print(ensure_compile_cache()); print(jax.config.jax_compilation_cache_dir)"
)


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(tmp_path):
    """Unset: <checkout>/.jax_cache — the same from this process and from
    one started in another directory (the path is part of the cache key)."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = _REPO
    out = _run(["-c", _PRINT_CACHE], env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    returned, configured = out.stdout.split()
    assert returned == configured == os.path.join(_REPO, ".jax_cache")
    assert backend.DEFAULT_COMPILE_CACHE_DIR == returned


def test_compile_cache_honours_the_environment(tmp_path):
    """Set: jax reads JAX_COMPILATION_CACHE_DIR itself; the helper touches
    nothing and nothing lands under the checkout."""
    placed = str(tmp_path / "placed_cache")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=placed,
               PYTHONPATH=_REPO)
    out = _run(["-c", _PRINT_CACHE], env=env, cwd=str(tmp_path))
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.split() == [placed, placed]


# ------------------------------------------------- kernels lower for the TPU


def _flash_grad(mesh):
    from dedloc_tpu.ops.flash_attention import flash_attention

    def loss(q, k, v, bias):
        out = flash_attention(q, k, v, bias, interpret=False, mesh=mesh)
        return jnp.sum(out.astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2))


def _ln_grad(mesh):
    from dedloc_tpu.ops.fused_ln import ln_residual

    def loss(x, r, gamma, beta):
        y = ln_residual(x, r, gamma, beta, interpret=False, mesh=mesh)
        return jnp.sum(y.astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3))


@pytest.mark.parametrize("n_devices", [1, 4])
def test_kernels_lower_for_tpu_on_one_device_and_under_a_mesh(n_devices):
    """jax.export for platform "tpu" at the recipe shapes (per-chip batch
    12, S=512, 16x64 heads, hidden 1024): Mosaic custom calls come out, on
    one device and — wrapped in shard_map — under a 4-device ("data",)
    mesh, where a bare pallas_call raises "Mosaic kernels cannot be
    automatically partitioned"."""
    batch = 12 * n_devices
    mesh = rows = repl = None
    if n_devices > 1:
        mesh = Mesh(np.array(jax.devices()[:n_devices]), ("data",))
        rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    qkv = jax.ShapeDtypeStruct((batch, 512, 16, 64), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((batch, 512), jnp.float32)
    hidden = jax.ShapeDtypeStruct((batch, 512, 1024), jnp.bfloat16)
    scale = jax.ShapeDtypeStruct((1024,), jnp.float32)
    cases = [
        (_flash_grad(mesh), (qkv, qkv, qkv, bias), (rows,) * 4),
        (_ln_grad(mesh), (hidden, hidden, scale, scale),
         (rows, rows, repl, repl)),
    ]
    for fn, args, shardings in cases:
        jitted = (
            jax.jit(fn) if mesh is None
            else jax.jit(fn, in_shardings=shardings)
        )
        exported = jax.export.export(jitted, platforms=["tpu"])(*args)
        assert exported.mlir_module().count("tpu_custom_call") >= 2  # fwd+bwd


# ------------------------------------------------- one process for each chip


class _Stop(Exception):
    pass


@pytest.mark.parametrize("module, entry", [
    ("aux", "run_aux"),
    ("coordinator", "run_coordinator"),
    ("dht_node", "run_dht_node"),
    ("gateway", "run_gateway"),
])
def test_host_only_roles_pin_themselves_to_the_cpu(monkeypatch, module,
                                                   entry):
    """aux / coordinator / dht_node / gateway never compute on the device,
    so on a TPU host they must not be able to take the chip a trainer is
    waiting for: each entry pins jax_platforms to "cpu" before it builds
    anything (here: before the DHT, which the test replaces with a stop)."""
    import importlib

    role = importlib.import_module(f"dedloc_tpu.roles.{module}")

    def stop(*_a, **_k):
        raise _Stop

    monkeypatch.setattr(role, "build_dht", stop)
    before = jax.config.jax_platforms
    jax.config.update("jax_platforms", None)  # as on an unpinned TPU host
    try:
        with pytest.raises(_Stop):
            getattr(role, entry)(parse_config(CollaborationArguments, []))
        assert jax.config.jax_platforms == "cpu"
    finally:
        jax.config.update("jax_platforms", before)


# ----------------------------------------- no device number off the device


def test_bench_at_real_size_refuses_a_non_tpu_backend():
    out = _run([os.path.join(_REPO, "bench.py")], cwd=_REPO,
               env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert "landed on 'cpu'" in out.stderr
    assert "{" not in out.stdout  # no metric line at all


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_peak_table_raises_on_an_unknown_tpu(monkeypatch):
    from dedloc_tpu.telemetry import steps

    def devices_are(platform, kind):
        monkeypatch.setattr(
            jax, "devices", lambda *a: [_FakeDevice(platform, kind)]
        )

    devices_are("tpu", "TPU v5 lite")
    assert steps.chip_peak_tflops() == 197.0
    devices_are("cpu", "cpu")
    assert steps.chip_peak_tflops() == 0.0
    devices_are("tpu", "TPU v99 experimental")
    with pytest.raises(ValueError, match="no peak TFLOP/s on record"):
        steps.chip_peak_tflops()


# ------------------------------------------------------- native wire codec


def test_native_binary_is_named_by_its_source(monkeypatch, tmp_path):
    """The loaded .so is named by a hash of native/wirecodec.cpp, so a
    binary built from any other source can never be picked up (a tree copy
    keeps no mtimes to judge staleness by)."""
    from dedloc_tpu import native

    with open(native._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(native._so_path()) == f"_wirecodec-{digest}.so"
    assert os.path.exists(native._so_path())  # the one this process loaded

    edited = tmp_path / "wirecodec.cpp"
    edited.write_bytes(open(native._SRC, "rb").read() + b"\n// edited\n")
    monkeypatch.setattr(native, "_SRC", str(edited))
    assert native._so_path() != os.path.join(
        os.path.dirname(native.__file__), f"_wirecodec-{digest}.so"
    )
    monkeypatch.setattr(native, "_SRC", str(tmp_path / "missing.cpp"))
    assert native._so_path() is None  # no source, no binary


def test_backend_description_matches_the_kernel_mode():
    info = backend.describe_backend()
    assert info["platform"] == "cpu" and info["device_count"] >= 1
    assert info["kernel_mode"] == "interpret"
    assert backend.pallas_interpret() is True


# ------------------------------ the real TPU compilers, without a chip


def test_flat_apply_and_kernels_compile_for_a_v5e_in_seconds():
    """libtpu's compile-only client runs XLA:TPU and Mosaic on this CPU
    (tools/tpu_aot.py): every Pallas kernel must get through Mosaic, and the
    fused flat apply must not take the ~20 minutes it took on the chip
    before PR 21 (two compile-time traps, invisible on the CPU: a
    constant-folded jnp.repeat and fused slice+reshape pairs)."""
    rows = _tpu_aot("flat_apply_step", "kernels")
    assert rows["flat_apply_step"]["compile_s"] < 60
    assert rows["kernels"]["compile_s"] < 60
    # flash fwd (x3), fused bwd (x2), the tiled bwd, ln fwd, ln bwd
    assert rows["kernels"]["tpu_custom_calls"] == 8
    # one tile at (12, 512, 16 x 64) and, causal, at D=128; tiles at S=2,048
    assert rows["kernels"]["flash_fwd_forms"] == {"one_tile": 2, "tiles": 1}
    # D=64 (two heads a lane tile) and D=128: a head's window is its block
    assert set(rows["kernels"]["flash_windows"]) == {
        "flash_fwd", "flash_bwd_fused", "flash_causal_fwd",
        "flash_causal_bwd_fused", "flash_bwd_tiled",
    }
    # ("block": no window metadata on the call, the parent's programs)
    assert set(rows["kernels"]["flash_windows"].values()) == {"block"}
    assert rows["kernels"]["device_kind"] == "TPU v5 lite"


def test_accumulate_step_has_no_relayout_copies_around_flash_attention():
    """The recipe's accumulate_step (ALBERT-large, B=12, S=512, flash +
    fused_ln), compiled for a v5e: the flash kernels read and write the
    model's own [B, S, H·D] layout, so the compiler puts no ``copy`` around
    them in the scanned layer bodies. 16 such copies per layer iteration
    before PR 24 (15 of them the kernels' [B·H, S, D] layout contract, 8.7 %
    of the device's time), 1 since (the layer input of a weight-gradient
    matmul, not attention's). The slack to 3 is for the compiler, not for a
    transpose left in — or put back into — the wrapper, which brings a
    per-head shape ([.., 16, 512, 64], [.., 192, 512, 64]) with it."""
    row = _tpu_aot("accumulate_step")["accumulate_step"]
    # S=512 under a 512 block: the scanned layer's one forward call
    assert row["flash_fwd_forms"] == {"one_tile": 1, "tiles": 0}
    assert row["flash_windows"] == {
        "flash_fwd": "block", "flash_bwd_fused": "block"
    }
    copies = row["layer_body_copies"]
    assert len(copies) <= 3, copies
    assert not [shape for shape in copies if shape.endswith(",512,64]")]
