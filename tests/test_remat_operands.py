"""The layer remat policy ``kernel_operands`` (``models/albert.py``): a
decoder layer keeps what its Pallas backward kernels READ — q / k / v as the
flash kernels take them, the short convolution's B | C | u — beside what the
forward ones wrote. For the three families that take it as their default:
the same bits as ``kernel_outputs`` and ``nothing`` (loss and every gradient
leaf); the engagement count with no chip (the projections that feed a kernel
run once a layer in the gradient, not twice; every kernel still once); and
the mechanism's counter ``remat.kept_bytes`` against the shapes' arithmetic
at the published widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.models.deepseek_v3 import DeepseekV3Config
from dedloc_tpu.models.lfm2_moe import Lfm2MoeConfig
from dedloc_tpu.models.ouro import OuroConfig
from dedloc_tpu.models.remat import remat_policy_object
from dedloc_tpu.models.sdar_moe import SdarMoeConfig
from dedloc_tpu.models.smallthinker import SmallThinkerConfig
from dedloc_tpu.parallel.train_step import stash_bytes
from dedloc_tpu.roles.common import (
    build_loss_fn,
    build_model,
    drop_collator_keys,
    model_family,
)

SEQ = 64
# test-sized, through the Pallas kernels in interpreter mode (a group of
# seven or eight wants heads of 128, LFM2's pairs heads of 64)
TINY = {
    # a global layer without positions and a banded one with RoPE
    "smallthinker": ("smallthinker_tiny", dict(
        head_dim=128, num_hidden_layers=2, attention_block_size=16,
        sliding_window_size=24,
    )),
    "sdar": ("sdar_tiny", dict(
        head_dim=128, num_hidden_layers=2, attention_block_size=16,
    )),
    # a dense conv layer, an expert attention layer, an expert conv layer; a
    # vocabulary whose width is no projection's (heads: 4 x 64 = 256)
    "lfm2": ("lfm2_tiny", dict(
        head_dim=64, num_hidden_layers=3, attention_block_size=16,
        vocab_size=320,
    )),
}
FAMILIES = sorted(TINY)


def _tiny(family, remat_policy):
    """(cfg, loss(params), params) of ``family``'s tiny model under one
    layer policy: flash attention, the family's own synthetic batch, seeded
    weights away from the initialiser's symmetry."""
    name, overrides = TINY[family]
    source = model_family(name)
    # float32 compute: in bf16 XLA's CPU fusions keep excess precision, so
    # there even ``kernel_outputs`` and ``nothing`` differ in the last bits
    cfg = source.config.named(name)(
        attention_impl="flash", remat_policy=remat_policy,
        dtype=jnp.float32, **overrides
    )
    model = source.module(cfg)
    batch = jax.tree.map(jnp.asarray, drop_collator_keys(
        next(source.synthetic_batches(cfg, 1, SEQ, 0))
    ))
    params = model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree.unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)
    ])
    loss_fn = build_loss_fn(model)
    return cfg, lambda p: loss_fn(p, batch, jax.random.PRNGKey(3))[0], params


def _default(family):
    return model_family(TINY[family][0]).config().remat_policy


@pytest.mark.parametrize("family", FAMILIES)
def test_the_default_policy_gives_the_same_bits(family):
    """The stash holds the values the forward computed and the backward
    reads them instead of recomputing the same values: loss and EVERY
    gradient leaf — the held experts' and, in LFM2, the bias leaves'
    cotangent (the load statistic: a replay that re-routes shows there) —
    equal those of ``kernel_outputs`` and of ``nothing``."""
    _cfg, loss, params = _tiny(family, _default(family))
    got_loss, got = jax.value_and_grad(loss)(params)
    for other in ("kernel_outputs", "nothing"):
        _cfg, ref_loss_fn, ref_params = _tiny(family, other)
        ref_loss, ref = jax.value_and_grad(ref_loss_fn)(ref_params)
        assert float(got_loss) == float(ref_loss), other
        jax.tree_util.tree_map_with_path(  # raises on a different tree, too
            lambda path, leaf, ref_leaf: np.testing.assert_array_equal(
                leaf, ref_leaf, err_msg=f"{other} {jax.tree_util.keystr(path)}"
            ),
            got, ref,
        )


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it (scan
    bodies, remat's replay, the custom VJPs' halves): one entry a SITE."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


def _sites(family, remat_policy):
    """(matmul sites [tokens, hidden] x [hidden, width] by width, Pallas
    call sites by kernel name) in the jaxpr of ``family``'s gradient."""
    cfg, loss, params = _tiny(family, remat_policy)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr  # traced, not run
    matmuls, kernels = {}, {}
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval.shape for v in eqn.invars)
            if len(rhs) == 2 and lhs[-1] == rhs[0] == cfg.hidden_size:
                matmuls[rhs[1]] = matmuls.get(rhs[1], 0) + 1
        elif eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            kernels[name] = kernels.get(name, 0) + 1
    return cfg, matmuls, kernels


def _projection_sites(family, cfg):
    """{width: (sites under ``kernel_outputs``, under ``kernel_operands``)}
    of the matmuls that feed a kernel. A projection whose output the kernel
    reads AS IT IS (SmallThinker's q / k / v — RoPE's backward is linear —,
    every v, LFM2's ``in_proj``) leaves the replay: forward + replay become
    the forward alone. One behind a per-head RMSNorm (SDAR's and LFM2's q
    and k) stays: the norm's backward reads the norm's INPUT."""
    heads = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim  # k_proj and v_proj
    layers = cfg.num_hidden_layers
    if family == "smallthinker":
        return {heads: (2 * layers, layers), kv: (4 * layers, 2 * layers)}
    if family == "sdar":
        return {heads: (2 * layers, 2 * layers), kv: (4 * layers, 3 * layers)}
    conv = sum(kind == "conv" for _index, kind, _sparse in cfg.layer_plan)
    attn = layers - conv
    return {
        3 * cfg.hidden_size: (2 * conv, conv),
        heads: (2 * attn, 2 * attn), kv: (4 * attn, 3 * attn),
    }


@pytest.mark.parametrize("family", FAMILIES)
def test_the_projections_that_feed_a_kernel_run_once(family):
    """The engagement count with no chip. Under ``kernel_outputs`` a layer's
    q / k / v (or ``in_proj``) matmul has two sites in the gradient — the
    forward's and the backward's replay of the layer; under
    ``kernel_operands`` the replay's is gone where the kernel's operand is
    all the backward needs (``_projection_sites``), and every kernel,
    forward and backward, still has the sites it had."""
    cfg, before, kernels_before = _sites(family, "kernel_outputs")
    _cfg, after, kernels_after = _sites(family, _default(family))
    expected = _projection_sites(family, cfg)
    assert {w: (before[w], after[w]) for w in expected} == expected
    # nothing else moved: the router, the experts, the head
    assert {w: n for w, n in before.items() if w not in expected} == {
        w: n for w, n in after.items() if w not in expected
    }
    assert kernels_after == kernels_before
    assert any(name.endswith("bwd_dq") for name in kernels_after)
    if family == "lfm2":
        assert kernels_after["short_conv_bwd"] == kernels_after[
            "short_conv_fwd"
        ]


# (model name, the cell's cut, operand bytes a micro-batch of one row at the
# cell's sequence length: q + k + v of an attention layer, B | C | u of a
# convolution layer, bf16)
PUBLISHED = {
    "smallthinker": (
        "smallthinker_21b_a3b", dict(num_hidden_layers=4, vocab_size=18992,
                                     expert_shard="0/8"), 16384,
        4 * 16384 * (28 + 2 * 4) * 128 * 2,
    ),
    "sdar": (
        "sdar_30b_a3b", dict(num_hidden_layers=4, vocab_size=18992,
                             expert_shard="0/8"), 4096,
        4 * 2 * 4096 * (32 + 2 * 4) * 128 * 2,  # both streams' positions
    ),
    "lfm2": (
        "lfm2_24b_a2b", dict(num_hidden_layers=5, vocab_size=8192,
                             expert_shard="0/8"), 4096,
        4 * 4096 * 3 * 2048 * 2 + 4096 * (32 + 2 * 8) * 64 * 2,
    ),
}


@pytest.mark.parametrize("family", FAMILIES)
def test_kept_bytes_is_the_shapes_arithmetic(family):
    """``remat.kept_bytes`` at the published widths and the benchmark
    cell's cut, from ``jax.eval_shape`` (nothing allocated): the new default
    keeps exactly the kernels' operands more than ``kernel_outputs`` — 151
    MB a layer in SmallThinker's cell."""
    name, cut, seq, operands = PUBLISHED[family]
    kept = {}
    for policy in ("", "kernel_outputs"):
        cfg, model = build_model(name, policy, **cut)
        ids = jax.ShapeDtypeStruct((1, seq), jnp.int32)
        params = jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
            jax.random.PRNGKey(0),
        )
        batch = jax.eval_shape(lambda: drop_collator_keys(
            next(model_family(cfg).synthetic_batches(cfg, 1, seq, 0))
        ))
        assert batch["input_ids"].shape == ids.shape
        kept[cfg.remat_policy] = stash_bytes(
            build_loss_fn(model), params, batch, jax.random.PRNGKey(0)
        )
    assert kept["kernel_operands"] - kept["kernel_outputs"] == operands
    if family == "smallthinker":
        assert operands // 4 == 150_994_944  # "151 MB a layer"
    # the layer inputs and the kernels' outputs are in both readings
    assert kept["kernel_outputs"] > operands // 2


def test_the_table_and_the_five_defaults():
    """One table: the new row resolves, a name that is none of its rows
    still raises, and each decoder family states the default its cell's
    memory allows."""
    assert callable(remat_policy_object("kernel_operands"))
    assert callable(remat_policy_object("kernel_outputs"))
    with pytest.raises(ValueError, match="unknown remat_policy"):
        remat_policy_object("kernel_operand")
    assert {
        cls.__name__: cls().remat_policy for cls in (
            SmallThinkerConfig, SdarMoeConfig, Lfm2MoeConfig,
            DeepseekV3Config, OuroConfig,
        )
    } == {
        "SmallThinkerConfig": "kernel_operands",
        "SdarMoeConfig": "kernel_operands",
        "Lfm2MoeConfig": "kernel_operands",
        "DeepseekV3Config": "kernel_outputs",
        "OuroConfig": "kernel_outputs",
    }
    # the override the trainer's flag passes reaches the model
    cfg, _model = build_model("smallthinker_tiny", "kernel_outputs")
    assert cfg.remat_policy == "kernel_outputs"
