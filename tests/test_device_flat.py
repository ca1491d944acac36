"""Device-resident flat gradient pipeline (averaging/device_flat.py):
parity with the host TreeLayout flatten and the native wire codec, hostile
shapes, error-feedback commit discipline, and the averager's flat fast
path. All tests are loopback-free and numerically locked — the device
pipeline must be bit-identical to the host flatten for fp32 and within the
codec's documented tolerance (one quantization code) for fp16/uint8."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu import native
from dedloc_tpu.averaging.device_flat import (
    DeviceFlatPipeline,
    named_device_leaves,
)
from dedloc_tpu.averaging.partition import FlatTree, TreeLayout
from dedloc_tpu.utils.checkpoint import tree_to_named

pytestmark = pytest.mark.wirepath


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _hostile_tree(rng):
    """Empty leaves, a scalar leaf, a nested branch, a non-contiguous
    source — the shapes the checkpoint path hardened against."""
    noncontig = np.asfortranarray(
        rng.standard_normal((6, 4)).astype(np.float32)
    )
    return {
        "a": {"kernel": jnp.asarray(rng.standard_normal((7, 5)), jnp.float32)},
        "b": jnp.asarray(rng.standard_normal((11,)), jnp.float32),
        "empty": jnp.zeros((0, 3), jnp.float32),
        "scalar": jnp.asarray(1.25, jnp.float32),
        "noncontig": jnp.asarray(noncontig),
    }


def _host_flat(tree, n=1):
    """The legacy host reference: per-leaf mean, tree_to_named naming,
    TreeLayout.flatten_into."""
    mean = jax.tree.map(lambda g: g / n, tree)
    named = tree_to_named(mean)
    layout = TreeLayout.for_tree(named)
    return layout.flatten_into(
        named, np.empty(layout.total_size, np.float32)
    ), layout


# ------------------------------------------------------------ fp32 parity


def test_device_flatten_bit_identical_to_host(rng):
    tree = _hostile_tree(rng)
    host, layout = _host_flat(tree, n=3)
    pipe = DeviceFlatPipeline.for_tree(tree, compression="none",
                                       chunk_elems=16)
    result = pipe.fetch(tree, n=3, use_ef=False).result()
    assert isinstance(result, FlatTree)
    np.testing.assert_array_equal(result.flat, host)
    # identical spec (names, shapes) as the host layout
    assert [(n_, tuple(s)) for n_, s, _d in pipe.spec] == [
        (n_, tuple(s)) for n_, s, _d in layout.spec
    ]


def test_device_clip_matches_host_formula(rng):
    tree = _hostile_tree(rng)
    host, _layout = _host_flat(tree, n=2)
    cap = 0.25
    gnorm = float(np.sqrt(np.vdot(host, host).real))
    scale = min(1.0, cap / (gnorm + 1e-12))
    pipe = DeviceFlatPipeline.for_tree(tree, compression="none",
                                       chunk_elems=16)
    result = pipe.fetch(tree, n=2, clip_cap=cap, use_ef=False).result()
    np.testing.assert_allclose(
        result.flat, host * np.float32(scale), rtol=2e-7, atol=1e-9
    )


def test_named_views_reconstruct_every_leaf(rng):
    tree = _hostile_tree(rng)
    host, layout = _host_flat(tree)
    result = DeviceFlatPipeline.for_tree(
        tree, compression="none", chunk_elems=8
    ).fetch(tree, use_ef=False).result()
    ref = layout.unflatten(host)
    assert set(result) == set(ref)
    for name in ref:
        np.testing.assert_array_equal(result[name], ref[name])


# ---------------------------------------------------- quantization parity


def test_fp16_wire_bit_identical_to_host_codec(rng):
    tree = _hostile_tree(rng)
    host, _ = _host_flat(tree)
    pipe = DeviceFlatPipeline.for_tree(tree, compression="float16",
                                       chunk_elems=16)
    fetch = pipe.fetch(tree, use_ef=True)
    result = fetch.result()
    # what the host F16C encode+decode round-trip would reconstruct
    np.testing.assert_array_equal(
        result.flat, native.f16_to_f32(native.f32_to_f16(host))
    )
    # the D2H transfer carried 2 bytes/elem, not 4
    assert fetch.wire_bytes == host.size * 2


def test_uint8_wire_within_one_code_of_host_codec(rng):
    tree = _hostile_tree(rng)
    host, _ = _host_flat(tree)
    block = 16
    pipe = DeviceFlatPipeline.for_tree(tree, compression="uint8",
                                       chunk_elems=block)
    fetch = pipe.fetch(tree, use_ef=True)
    result = fetch.result()
    # host reference: native affine quantizer per block (the documented
    # tolerance is ONE quantization code — rint boundary cases may round
    # differently between the device program and the host codec)
    worst = 0.0
    for off in range(0, host.size, block):
        blk = host[off:off + block]
        q, lo, sc = native.quantize_uint8(blk)
        ref = native.dequantize_uint8(q, lo, sc)
        diff = np.max(np.abs(result.flat[off:off + block] - ref), initial=0.0)
        worst = max(worst, float(diff / sc))
    assert worst <= 1.0 + 1e-5, (
        f"device uint8 grid drifted {worst:.3f} codes from the host codec"
    )
    # 1 byte/elem + per-block (lo, scale) fp32 pairs
    n_blocks = -(-host.size // block)
    assert fetch.wire_bytes == host.size + n_blocks * 8


def test_uint8_blocks_use_independent_grids(rng):
    # one cold block next to a hot block: a whole-vector grid would
    # flatten the cold block to ~1 code; per-block grids keep it sharp
    tree = {
        "cold": jnp.asarray(rng.standard_normal(64) * 1e-4, jnp.float32),
        "hot": jnp.asarray(rng.standard_normal(64) * 1e3, jnp.float32),
    }
    host, _ = _host_flat(tree)
    pipe = DeviceFlatPipeline.for_tree(tree, compression="uint8",
                                       chunk_elems=64)
    result = pipe.fetch(tree, use_ef=False).result()
    cold = np.asarray(result["['cold']"])
    err = np.max(np.abs(cold - np.asarray(jax.device_get(tree["cold"]))))
    # cold block quantized on its OWN 1e-4-wide grid: error ~4e-7, not ~8
    assert err < 1e-5


# ------------------------------------------------------------ refusals


def test_non_float_leaves_refused_like_checkpoint_path():
    with pytest.raises(ValueError, match="refuses non-float"):
        DeviceFlatPipeline.for_tree({"counts": jnp.zeros((3,), jnp.int32)})
    with pytest.raises(ValueError, match="refuses non-float"):
        DeviceFlatPipeline.for_tree({
            "ok": jnp.zeros((3,), jnp.float32),
            "bad": jnp.zeros((2,), bool),
        })


def test_mixed_float_dtypes_accepted_and_widened(rng):
    # bf16/fp16 leaves widen exactly to fp32 — same values as the host
    # flatten's unsafe cast
    tree = {
        "f32": jnp.asarray(rng.standard_normal(5), jnp.float32),
        "bf16": jnp.asarray(rng.standard_normal(5), jnp.bfloat16),
        "f16": jnp.asarray(rng.standard_normal(5), jnp.float16),
    }
    host_named = tree_to_named(tree)
    layout = TreeLayout.for_tree(host_named)
    # the host layout records the ORIGINAL dtypes; the device spec is
    # uniformly fp32 — compare values, which must agree exactly
    host = np.concatenate([
        np.asarray(host_named[name], np.float32).reshape(-1)
        for name in sorted(host_named)
    ])
    result = DeviceFlatPipeline.for_tree(
        tree, compression="none"
    ).fetch(tree, use_ef=False).result()
    np.testing.assert_array_equal(result.flat, host)
    assert layout.total_size == result.flat.size


# -------------------------------------------------- error-feedback device


def test_device_ef_commit_discipline(rng):
    tree = _hostile_tree(rng)
    pipe = DeviceFlatPipeline.for_tree(tree, compression="uint8",
                                       chunk_elems=16)
    f1 = pipe.fetch(tree, use_ef=True)
    f1.result()
    assert pipe.residual_norm() == 0.0, "uncommitted rounds leave no trace"
    # a RETRY re-derives the same contribution (residual unchanged)
    f2 = pipe.fetch(tree, use_ef=True)
    np.testing.assert_array_equal(f2.result().flat, f1.result().flat)
    pipe.commit(f2)
    assert pipe.residual_norm() > 0
    # the non-blocking form a reader on the round's path uses: a device
    # scalar on its way to the host, the same number when read
    launched = pipe.residual_norm_launch()
    assert not isinstance(launched, float)
    assert float(launched) == pipe.residual_norm()
    # post-resync reset
    pipe.reset_residual()
    assert pipe.residual_norm() == 0.0
    assert pipe.residual_norm_launch() == 0.0


def test_device_ef_uint8_drift_free_over_rounds(rng):
    """The flat-pipeline form of the DGC guarantee: cumulative applied
    signal tracks the cumulative true gradient to within ONE residual —
    bounded, not growing — over 25 committed uint8 rounds."""
    tree = {"w": jnp.zeros((64,), jnp.float32)}
    pipe = DeviceFlatPipeline.for_tree(tree, compression="uint8",
                                       chunk_elems=32)
    true_sum = np.zeros(64, np.float32)
    applied_sum = np.zeros(64, np.float32)
    drifts = []
    for r in range(25):
        g = rng.standard_normal(64).astype(np.float32)
        true_sum += g
        fetch = pipe.fetch({"w": jnp.asarray(g)}, use_ef=True)
        applied_sum += fetch.result().flat
        pipe.commit(fetch)
        drifts.append(float(np.max(np.abs(applied_sum - true_sum))))
    # the drift equals the carried residual: bounded by one quantization
    # step of a single round, and NOT growing with round count
    assert drifts[-1] < 0.1
    assert max(drifts) < 0.1
    # without error feedback the same wire drifts far more
    pipe_no_ef = DeviceFlatPipeline.for_tree(tree, compression="uint8",
                                             chunk_elems=32)
    rng2 = np.random.default_rng(0)
    true2 = np.zeros(64, np.float32)
    applied2 = np.zeros(64, np.float32)
    for r in range(25):
        g = rng2.standard_normal(64).astype(np.float32)
        true2 += g
        applied2 += pipe_no_ef.fetch(
            {"w": jnp.asarray(g)}, use_ef=False
        ).result().flat
    assert np.max(np.abs(applied2 - true2)) > drifts[-1]


# -------------------------------------------------------- fetch mechanics


def test_double_buffering_allows_two_outstanding_fetches(rng):
    tree = _hostile_tree(rng)
    pipe = DeviceFlatPipeline.for_tree(tree, compression="none",
                                       chunk_elems=16)
    f1 = pipe.fetch(tree, n=1, use_ef=False)
    f2 = pipe.fetch(tree, n=2, use_ef=False)
    host1, _ = _host_flat(tree, n=1)
    host2, _ = _host_flat(tree, n=2)
    np.testing.assert_array_equal(f1.result().flat, host1)
    np.testing.assert_array_equal(f2.result().flat, host2)


def test_result_is_idempotent_and_thread_safe(rng):
    import threading

    tree = _hostile_tree(rng)
    pipe = DeviceFlatPipeline.for_tree(tree, compression="float16",
                                       chunk_elems=16)
    fetch = pipe.fetch(tree, use_ef=False)
    results = []
    threads = [
        threading.Thread(target=lambda: results.append(fetch.result()))
        for _ in range(4)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert all(r is results[0] for r in results)


def test_matches_tree_detects_schema_change(rng):
    tree = _hostile_tree(rng)
    pipe = DeviceFlatPipeline.for_tree(tree)
    assert pipe.matches_tree(tree)
    changed = dict(tree)
    changed["b"] = jnp.zeros((12,), jnp.float32)  # different shape
    assert not pipe.matches_tree(changed)
    assert not pipe.matches_tree({"only": jnp.zeros((1,), jnp.float32)})


def test_named_device_leaves_matchestree_to_named_naming(rng):
    tree = _hostile_tree(rng)
    host_names = sorted(tree_to_named(tree))
    dev_names = sorted(name for name, _leaf in named_device_leaves(tree))
    assert host_names == dev_names


# -------------------------------------------------- averager fast path


def test_averager_spec_fingerprint_matches_schema_fingerprint(rng):
    from dedloc_tpu.averaging.averager import (
        schema_fingerprint,
        spec_fingerprint,
    )

    tree = _hostile_tree(rng)
    host, layout = _host_flat(tree)
    named = layout.unflatten(host)
    pipe = DeviceFlatPipeline.for_tree(tree)
    assert spec_fingerprint(pipe.spec) == schema_fingerprint(named)


def test_tree_view_round_trips_flatten(rng):
    tree = _hostile_tree(rng)
    host, layout = _host_flat(tree)
    view = layout.tree_view(host)
    assert isinstance(view, FlatTree)
    assert view.flat is host
    # re-flattening the view writes back the identical buffer
    out = layout.flatten_into(view, np.empty_like(host))
    np.testing.assert_array_equal(out, host)
