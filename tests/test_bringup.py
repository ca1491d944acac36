"""What chip bring-up promises, as far as a CPU can check it: the smoke
refuses to pass without a chip, the compile cache sits where it is told,
the Pallas kernels lower for TPU on one device AND under a mesh, host-only
roles cannot take the chip, and nothing reports a device rate off-device."""
import hashlib
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.utils import backend

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(argv, env=None, cwd=None, timeout=120):
    return subprocess.run(
        [sys.executable, *argv], env=env, cwd=cwd, timeout=timeout,
        capture_output=True, text=True,
    )


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


def _tpu_aot(*programs):
    """tools/tpu_aot.py's rows by program, compiled in a child (the real
    XLA:TPU and Mosaic compilers, through libtpu's compile-only client)."""
    import json

    out = _run(
        [os.path.join(_REPO, "tools", "tpu_aot.py"), *programs],
        timeout=300,  # a pathological compile fails here, not after an hour
    )
    if out.returncode == 3:
        pytest.skip(out.stderr.strip().splitlines()[-1])
    assert out.returncode == 0, out.stderr[-3000:]
    return {
        row["program"]: row
        for row in map(json.loads, out.stdout.strip().splitlines())
    }


def test_flat_apply_and_kernels_compile_for_a_v5e_in_seconds():
    """libtpu's compile-only client runs XLA:TPU and Mosaic on this CPU
    (tools/tpu_aot.py): every Pallas kernel must get through Mosaic, and the
    fused flat apply must not take the ~20 minutes it took on the chip
    before PR 21 (two compile-time traps, invisible on the CPU: a
    constant-folded jnp.repeat and fused slice+reshape pairs)."""
    rows = _tpu_aot("flat_apply_step", "kernels")
    assert rows["flat_apply_step"]["compile_s"] < 60
    assert rows["kernels"]["compile_s"] < 60
    # flash fwd (x3), fused bwd (x2), dq, dkv, ln fwd, ln bwd
    assert rows["kernels"]["tpu_custom_calls"] == 9
    # one tile at (12, 512, 16 x 64) and, causal, at D=128; tiles at S=2,048
    assert rows["kernels"]["flash_fwd_forms"] == {"one_tile": 2, "tiles": 1}
    # D=64 (two heads a lane tile) and D=128: a head's window is its block
    assert set(rows["kernels"]["flash_windows"]) == {
        "flash_fwd", "flash_bwd_fused", "flash_causal_fwd",
        "flash_causal_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv",
    }
    # ("block": no window metadata on the call, the parent's programs)
    assert set(rows["kernels"]["flash_windows"].values()) == {"block"}
    assert rows["kernels"]["device_kind"] == "TPU v5 lite"


def test_two_width_kernels_contract_over_a_heads_own_lane_tiles():
    """Latent attention (q/k 192 wide, v and out 128; two heads a column
    block of 384 / 256 lanes), compiled for a v5e alone and inside
    kanana-2's accumulate_step at the cell's cut: every per-head product
    of the three kernels contracts over, and lands in, the head's own lane
    window — 256 of the 384 q/k lanes, the head's own 128-lane tile of v,
    dO and out — as each call's metadata says (``flash_windows``)."""
    rows = _tpu_aot("mla_kernels", "kanana_accumulate_step")
    windowed = {
        "qk_window": 256, "qk_block": 384, "v_window": 128, "v_block": 256,
    }
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_mla_fwd": windowed, "flash_mla_bwd_dq": windowed,
            "flash_mla_bwd_dkv": windowed,
        }
    # the dense layer and the scanned expert layers: a forward site each
    assert rows["kanana_accumulate_step"]["flash_fwd_forms"] == {
        "one_tile": 0, "tiles": 2
    }
    # the routed loop's backward sums into the accumulator's expert leaves
    # (gradient sinks): no add pass of its own over one (3 before PR 33)
    passes = rows["kanana_accumulate_step"]["expert_grad_passes"]
    assert passes["adds"] == 0
    # the routed walk (PR 42): a bulk and a tail loop a direction in the
    # scanned layer's body (2 loops with the single-size walk), the three
    # ``old + term`` adds of each backward loop riding their dots' fusions
    assert (passes["tile_loops"], passes["fused_adds"],
            passes["loose_adds"]) == (4, 6, 0)
    # the held matrices arrive in bf16 (PR 50: the step's compute-dtype
    # copies, stacked like the leaves): no whole-matrix float32 -> bf16 pass
    # inside the program (3 before: XLA hoisted the stack's casts out of the
    # scan), and the bf16 stack's 0.30 GB of scratch gone (3,557,284,864)
    assert passes["held_casts"] == 0
    assert rows["kanana_accumulate_step"]["memory"]["temp_bytes"] <= 3.3e9
    # 0.05 GB under the line: the layers keep the kernels' OUTPUTS alone
    assert rows["kanana_accumulate_step"]["remat_policy"] == "kernel_outputs"


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


def test_ouro_accumulate_step_keeps_the_flash_outputs_and_fits_the_cap():
    """The looped decoder's accumulate_step (Ouro-2.6B cut to the cell's 3
    layers, 1 row of 4,096), compiled for a v5e: the layer's remat policy
    keeps the causal flash kernel's out + lse, so the lowered module calls
    the forward kernel ONCE (the forward scan's body) and the backward's
    replay of the layer holds none — 2 call sites under policy ``nothing``,
    12 of 24 executions a micro-batch (PR 28). The stash is paid in the
    program's scratch: 5.30 GB against 5.04 — 5.16 since the kernels'
    operands sit behind ``decoder.GroupedQueryAttention``'s barrier (PR 45:
    the 11 float32 relayouts of RoPE's pieces left the layer bodies) —
    which with a draining snapshot's 9.96 GB of state stays under the 15.3
    GB the cell is sized by; a policy that also kept ``flash_qkv`` would
    read 6.2 GB here."""
    row = _tpu_aot("ouro_accumulate_step")["ouro_accumulate_step"]
    assert row["remat_policy"] == "kernel_outputs"
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 1}
    assert row["flash_windows"] == {  # D=128: a head is one lane tile
        name: "block" for name in (
            "flash_causal_fwd", "flash_causal_bwd_dq", "flash_causal_bwd_dkv"
        )
    }
    # forward, dq, dkv: one site each
    assert row["tpu_custom_calls"] == 3
    assert row["memory"]["temp_bytes"] <= 5.2e9, row["memory"]
    copies = row["layer_body_copies"]
    assert not [shape for shape in copies if shape.startswith("f32")], copies


def test_lfm2_accumulate_step_keeps_what_its_backward_reads():
    """LFM2-24B-A2B at the cell's cut (5 layers: four short-convolution
    mixers, one grouped-query attention; 1 row of 4,096), compiled for a
    v5e alone and inside its accumulate_step: the grouped kernels read k / v
    at 8 heads beside q's 32 (their metadata says so; no window metadata);
    under remat ``kernel_outputs`` every kernel's outputs are kept, so each
    forward kernel has ONE call site per mixer and the backward replays
    none — short_conv 4 + 4, flash_gqa 1 + 1 + 1; and the program's scratch
    beside 28 bytes a parameter of state with a draining snapshot (13.14 GB)
    stays under the allocator's 16.91 GB with 1 GB to spare."""
    rows = _tpu_aot("gqa_kernels", "lfm2_accumulate_step")
    heads = {"heads": 32, "kv_heads": 8}
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_gqa_fwd": heads, "flash_gqa_bwd_dq": heads,
            "flash_gqa_bwd_dkv": heads,
        }
    row = rows["lfm2_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_gqa_fwd": 1, "flash_gqa_bwd_dq": 1, "flash_gqa_bwd_dkv": 1,
        "short_conv_fwd": 4, "short_conv_bwd": 4,
    }
    assert row["tpu_custom_calls"] == 11
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 1}
    assert 469_285_248 * 28 + row["memory"]["temp_bytes"] <= 15.9e9
    # gradient sinks (PR 33): the tile loops' backward starts from the
    # accumulator's twelve expert leaves and leaves the sums there — no
    # zeroed float32 carry, no ``grad_acc + result`` pass (12 + 12 before),
    # and the scratch those buffers took is gone (1,170,841,600 before)
    # … no float32 -> bf16 pass over a held matrix (PR 50: 24 before, the
    # forward's and the remat replay's twelve; the step is handed the bf16
    # matrices, cast once a global step: ``held_casts``) …
    # … and the walk's loops (PR 42): four routed layers x two directions x
    # the bulk and the tail loop (8 loops with the single-size walk),
    # every backward loop's three ``old + term`` adds inside the fusion of
    # their weight-gradient dot: a slice read and written once, no ``term``
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert row["memory"]["temp_bytes"] <= 1_170_841_600
    # since PR 41 the conv layers keep B | C | u and the attention layer
    # q / k / v for their backward kernels (remat ``kernel_operands``: the
    # call sites above are unchanged): 721,006,080 bytes of scratch against
    # 657,255,424 under ``kernel_outputs`` (1,028,988,928 without the
    # barrier before the flash call); since PR 46 every layer the stream
    # after its mixer and the attention layer its q / k norm's input too
    # (remat ``whole_mixer``, +104,857,600 kept): 814,876,672
    assert row["remat_policy"] == "whole_mixer"
    assert row["memory"]["temp_bytes"] <= 0.84e9


def test_smallthinker_accumulate_step_takes_the_band_and_a_group_of_seven():
    """SmallThinker-21BA3B at the cell's cut (one period: a global NoPE
    layer and three band-4096 RoPE layers; 1 row of 16,384), compiled for a
    v5e alone and inside its accumulate_step: the band kernels carry their
    band and head counts (28 over 4: a whole group of seven a program gets
    through Mosaic — the backward kernels inside the default scoped VMEM,
    the forward with the 23.75 MiB it asks for since its heads overlap,
    ``_fwd_vmem``), the global layer's are
    the grouped causal kernels with the metadata they always had; under
    remat ``kernel_outputs`` no kernel is replayed — 3 + 1 sites a kind;
    the ReLU-gated tile loop's backward sums into the accumulator's twelve
    expert leaves (gradient sinks); and the program's scratch beside 28
    bytes a parameter of state with a draining snapshot stays under the
    15.3 GB line this tree's cells are sized under."""
    rows = _tpu_aot("band_kernels", "smallthinker_accumulate_step")
    heads = {"heads": 28, "kv_heads": 4}
    band = dict(heads, band=4096)
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_band_fwd": band, "flash_band_bwd_dq": band,
            "flash_band_bwd_dkv": band, "flash_gqa_fwd": heads,
            "flash_gqa_bwd_dq": heads, "flash_gqa_bwd_dkv": heads,
        }
    row = rows["smallthinker_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_band_fwd": 3, "flash_band_bwd_dq": 3, "flash_band_bwd_dkv": 3,
        "flash_gqa_fwd": 1, "flash_gqa_bwd_dq": 1, "flash_gqa_bwd_dkv": 1,
    }
    assert row["tpu_custom_calls"] == 12
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 4}
    # … and the walk's loops (PR 42): four routed layers x two directions x
    # the bulk and the tail loop (8 loops with the single-size walk),
    # every backward loop's three ``old + term`` adds inside the fusion of
    # their weight-gradient dot: a slice read and written once, no ``term``
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert 370_547_200 * 28 + row["memory"]["temp_bytes"] <= 15.3e9
    # since PR 41 the layers keep q / k / v for their backward kernels
    # (remat ``kernel_operands``: still no kernel replayed, above) as the
    # bf16 buffers the kernels read: 2,530,225,152 bytes of scratch against
    # 2,504,165,376 under ``kernel_outputs``. Without the barrier before the
    # flash call XLA keeps the float32 pieces of RoPE's last add instead
    # (4,004,325,376); since PR 46 the stream after attention too (remat
    # ``whole_mixer``, +335,544,320 kept): 2,913,385,984
    assert row["remat_policy"] == "whole_mixer"
    assert row["memory"]["temp_bytes"] <= 3.0e9


def test_sdar_accumulate_step_takes_the_block_rule_and_a_group_of_eight():
    """SDAR-30B-A3B-Chat at the cell's cut (four layers; 1 row of 4,096
    clean tokens = 8,192 positions, a noisy stream then a clean one),
    compiled for a v5e alone and inside its accumulate_step: the
    block-diffusion kernels carry their blocks (4), their streams' length
    (4,096) and their head counts (32 over 4: a whole group of eight a
    program gets through Mosaic); under remat ``kernel_outputs`` no kernel
    is replayed — 4 sites a kernel, one a layer of the unrolled period; the
    SiLU-gated tile loop's backward sums into the accumulator's twelve
    expert leaves (a scan over single layers zero-filled, copied and cast
    3.0 GB of stacked expert matrices: ``models/sdar_moe._Period``); and the
    program's scratch beside 28 bytes a parameter of state with a draining
    snapshot stays under the 15.3 GB line this tree's cells are sized
    under."""
    rows = _tpu_aot("bd_kernels", "sdar_accumulate_step")
    blocks = {"heads": 32, "kv_heads": 4, "block": 4, "stream": 4096}
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_bd_fwd": blocks, "flash_bd_bwd_dq": blocks,
            "flash_bd_bwd_dkv": blocks,
        }
    row = rows["sdar_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_bd_fwd": 4, "flash_bd_bwd_dq": 4, "flash_bd_bwd_dkv": 4,
    }
    assert row["tpu_custom_calls"] == 12
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 4}
    # … and the walk's loops (PR 42): four routed layers x two directions x
    # the bulk and the tail loop (8 loops with the single-size walk),
    # every backward loop's three ``old + term`` adds inside the fusion of
    # their weight-gradient dot: a slice read and written once, no ``term``
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert row["layer_body_copies"] == []
    assert 456_346_624 * 28 + row["memory"]["temp_bytes"] <= 15.3e9
    # since PR 41 the layers keep q / k / v for their backward kernels (remat
    # ``kernel_operands``) behind a barrier that makes them buffers of their
    # own: 1,282,795,008 bytes of scratch — UNDER the 1,574,085,632 the
    # program read before either (2,545,200,128 without the barrier); since
    # PR 46 the q / k norm's input and the stream after attention too (remat
    # ``whole_mixer``, +436,207,616 kept): 1,697,340,416
    assert row["remat_policy"] == "whole_mixer"
    assert row["memory"]["temp_bytes"] <= 1.75e9


def test_laguna_accumulate_step_takes_a_band_equal_to_the_tile_and_a_group_of_six():
    """Laguna-XS.2 at the cell's cut (the dense layer + a period of three
    window-512 layers and a full one; 1 row of 8,192), compiled for a v5e
    alone and inside its accumulate_step: the band kernels carry their band
    (512 = the tile) and their head counts (64 over 8), the full layers'
    grouped causal ones theirs (48 over 8: a whole group of SIX a program
    gets through Mosaic), and the per-head gate's pair compiles at both head
    counts; under remat ``whole_mixer`` no flash kernel is replayed — 3
    sites a band kernel, 2 a full one — and the gate's forward is (10 sites
    for 5 layers: its output is kept by no rung, ``remat.REPLAYED_KERNELS``);
    the gate writes no float32 array of the context's size (XLA's expression
    wrote 7,267 MB of float32 under ``attn_gate``: PR 48) and adds no
    relayout copy to a layer body, the tile loop's backward sums into the
    accumulator's twelve expert leaves; and the program's scratch beside 28
    bytes a parameter of state with a draining snapshot stays under the 15.3
    GB line this tree's cells are sized under."""
    rows = _tpu_aot(
        "laguna_kernels", "head_gate_kernels", "laguna_accumulate_step"
    )
    band = {"heads": 64, "kv_heads": 8, "band": 512}
    full = {"heads": 48, "kv_heads": 8}
    for name in ("laguna_kernels", "laguna_accumulate_step"):
        assert rows[name]["flash_windows"] == {
            "flash_band_fwd": band, "flash_band_bwd_dq": band,
            "flash_band_bwd_dkv": band, "flash_gqa_fwd": full,
            "flash_gqa_bwd_dq": full, "flash_gqa_bwd_dkv": full,
        }
    assert rows["head_gate_kernels"]["kernel_calls"] == {
        "head_gate_fwd": 2, "head_gate_bwd": 2,  # 64 heads, 48 heads
    }
    row = rows["laguna_accumulate_step"]
    assert row["kernel_calls"] == {
        "flash_band_fwd": 3, "flash_band_bwd_dq": 3, "flash_band_bwd_dkv": 3,
        "flash_gqa_fwd": 2, "flash_gqa_bwd_dq": 2, "flash_gqa_bwd_dkv": 2,
        "head_gate_fwd": 10, "head_gate_bwd": 5,
    }
    assert row["tpu_custom_calls"] == 30
    assert row["flash_fwd_forms"] == {"one_tile": 0, "tiles": 5}
    assert row["expert_grad_passes"] == {
        "adds": 0, "zero_fills": 0, "tile_loops": 16, "fused_adds": 24,
        "loose_adds": 0, "held_casts": 0,
    }
    assert row["layer_body_copies"] == []
    # the gates and their gradients, [1, 8192, 64 | 48]: 9.4 MB
    assert row["attn_gate_float32_mb"] <= 32
    assert row["remat_policy"] == "whole_mixer"
    # 2,466,401,792 bytes of scratch beside 10.91 GB (2,935,357,952 with
    # the gate as XLA's expression, PR 47; 2,984,545,792 with the kernel's
    # output kept)
    assert row["memory"]["temp_bytes"] <= 2.6e9
    assert 389_634_048 * 28 + row["memory"]["temp_bytes"] <= 15.3e9
