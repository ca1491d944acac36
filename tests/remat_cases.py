"""The cases of ``tests/test_remat_operands*.py`` and
``tests/test_keye_remat.py`` (not a test file): a family's tiny model
through the Pallas kernels in interpreter mode under one layer remat policy,
its gradients and the sites of its gradient's jaxpr — each computed once a
process —, the kept bytes at the published widths, and the three assertions
the families' files bind to their rows. A file a family, so that
``--dist loadfile`` can spread what was one worker's twelve minutes."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from decoder_cases import perturbed
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
    # a dense full-attention layer (6 / 2 heads, half a head rotated) and a
    # sparse sliding one (8 / 2) at a band equal to the tile, a gate a head
    # on the kernels' output; a vocabulary whose width is no projection's
    "laguna": ("laguna_tiny", dict(
        head_dim=128, num_hidden_layers=2, attention_block_size=16,
        sliding_window=16, vocab_size=320,
    )),
}
# the rung PR 41 added and the one above it, the three families' default
POLICIES = ("kernel_operands", "whole_mixer")


def _build(family, remat_policy):
    """(source, cfg, model) of ``family``'s tiny model under one layer
    policy: flash attention, float32 compute (in bf16 XLA's CPU fusions
    keep excess precision, so there even ``kernel_outputs`` and ``nothing``
    differ in the last bits)."""
    name, overrides = TINY[family]
    source = model_family(name)
    cfg = source.config.named(name)(
        attention_impl="flash", remat_policy=remat_policy,
        dtype=jnp.float32, **overrides
    )
    return source, cfg, source.module(cfg)


def initialised(family, remat_policy):
    """(batch, params): the family's own synthetic batch and seeded
    weights away from the initialiser's symmetry."""
    source, cfg, model = _build(family, remat_policy)
    batch = jax.tree.map(jnp.asarray, drop_collator_keys(
        next(source.synthetic_batches(cfg, 1, SEQ, 0))
    ))
    return batch, perturbed(
        model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    )


@functools.lru_cache(maxsize=None)
def _values(family):
    """The policy decides what a layer KEEPS, not what ``model.init``
    returns (``check_the_parameters_do_not_depend_on_the_policy``): batch
    and parameters are built once a family."""
    return initialised(family, "kernel_outputs")


def _tiny(family, remat_policy):
    """(cfg, loss(params), params) of ``family``'s tiny model under one
    layer policy."""
    _source, cfg, model = _build(family, remat_policy)
    batch, params = _values(family)
    loss_fn = build_loss_fn(model)
    return cfg, lambda p: loss_fn(p, batch, jax.random.PRNGKey(3))[0], params


def check_the_parameters_do_not_depend_on_the_policy(family):
    _batch, params = _values(family)
    other_batch, other = initialised(family, "nothing")
    for got, want in ((other_batch, _batch), (other, params)):
        jax.tree_util.tree_map_with_path(  # raises on a different tree, too
            lambda path, leaf, ref_leaf: np.testing.assert_array_equal(
                leaf, ref_leaf, err_msg=jax.tree_util.keystr(path)
            ),
            got, want,
        )


@functools.lru_cache(maxsize=None)  # a reference is run once a family
def _loss_and_grad(family, remat_policy):
    _cfg, loss, params = _tiny(family, remat_policy)
    return jax.value_and_grad(loss)(params)


def check_the_default_policy_gives_the_same_bits(family, policy):
    """The stash holds the values the forward computed and the backward
    reads them instead of recomputing the same values: loss and EVERY
    gradient leaf — the held experts' and, in LFM2, the bias leaves'
    cotangent (the load statistic: a replay that re-routes shows there) —
    equal those of ``kernel_outputs`` and of ``nothing``."""
    got_loss, got = _loss_and_grad(family, policy)
    for other in ("kernel_outputs", "nothing"):
        ref_loss, ref = _loss_and_grad(family, other)
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


@functools.lru_cache(maxsize=None)
def _sites(family, remat_policy):
    """(matmul sites [tokens, in] x [in, out] by (in, out) — the forward's
    form, contracted over the weight's ROWS: a backward's ``g @ Wᵀ`` and
    ``xᵀ @ g`` are not —, Pallas call sites by kernel name) in the jaxpr of
    ``family``'s gradient."""
    cfg, loss, params = _tiny(family, remat_policy)
    jaxpr = jax.make_jaxpr(jax.grad(loss))(params).jaxpr  # traced, not run
    matmuls, kernels = {}, {}
    for eqn in _equations(jaxpr):
        if eqn.primitive.name == "dot_general":
            lhs, rhs = (v.aval for v in eqn.invars)
            (over_lhs, over_rhs), _batch = eqn.params["dimension_numbers"]
            if rhs.ndim == 2 and (tuple(over_lhs), tuple(over_rhs)) == (
                (lhs.ndim - 1,), (0,)
            ):
                matmuls[rhs.shape] = matmuls.get(rhs.shape, 0) + 1
        elif eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            kernels[name] = kernels.get(name, 0) + 1
    return cfg, matmuls, kernels


def _projection_sites(family, cfg):
    """{(in, out): sites under (``kernel_outputs``, ``kernel_operands``,
    ``whole_mixer``)} of the mixers' matmuls. A projection whose output the
    kernel reads AS IT IS (SmallThinker's q / k / v — RoPE's backward is
    linear —, every v, LFM2's ``in_proj``) leaves the replay under
    ``kernel_operands``: forward + replay become the forward alone. One
    behind a per-head RMSNorm (SDAR's and LFM2's q and k) stays until the
    norm's INPUT, which the norm's backward reads, is kept, and the
    out-projection until the sum it is added into is: ``whole_mixer``."""
    hidden = cfg.hidden_size
    heads = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim  # k_proj and v_proj
    layers = cfg.num_hidden_layers
    if family == "laguna":
        # a width a KIND of layer (one layer of each here): q / k / v leave
        # the replay with the kernels' operands, the out-projection and the
        # gate's ``g_proj`` with the sum and the gate's logits
        widths = [n for _kind, n, _sparse in cfg.layer_plan]
        return {
            **{(hidden, n * cfg.head_dim): (2, 1, 1) for n in widths},
            (hidden, kv): (4 * layers, 2 * layers, 2 * layers),
            **{(n * cfg.head_dim, hidden): (2, 2, 1) for n in widths},
            **{(hidden, n): (2, 2, 1) for n in widths},
        }
    if family == "smallthinker":
        return {
            (hidden, heads): (2 * layers, layers, layers),
            (hidden, kv): (4 * layers, 2 * layers, 2 * layers),
            (heads, hidden): (2 * layers, 2 * layers, layers),
        }
    if family == "sdar":
        return {
            (hidden, heads): (2 * layers, 2 * layers, layers),
            (hidden, kv): (4 * layers, 3 * layers, 2 * layers),
            (heads, hidden): (2 * layers, 2 * layers, layers),
        }
    conv = sum(kind == "conv" for _index, kind, _sparse in cfg.layer_plan)
    attn = layers - conv
    return {
        (hidden, 3 * hidden): (2 * conv, conv, conv),
        (hidden, hidden): (2 * conv, 2 * conv, conv),  # a conv's out_proj
        (hidden, heads): (2 * attn, 2 * attn, attn),
        (hidden, kv): (4 * attn, 3 * attn, 2 * attn),
        (heads, hidden): (2 * attn, 2 * attn, attn),
    }


def check_the_projections_that_feed_a_kernel_run_once(family, policy):
    """The engagement count with no chip. Under ``kernel_outputs`` a mixer's
    matmul has two sites in the gradient — the forward's and the backward's
    replay of the layer; under ``kernel_operands`` the replay's is gone
    where the kernel's operand is all the backward needs, under
    ``whole_mixer`` for every matmul of the mixer (``_projection_sites``),
    and every kernel, forward and backward, still has the sites it had."""
    cfg, before, kernels_before = _sites(family, "kernel_outputs")
    _cfg, after, kernels_after = _sites(family, policy)
    expected = {
        shape: (sites[0], sites[1 + POLICIES.index(policy)])
        for shape, sites in _projection_sites(family, cfg).items()
    }
    assert {w: (before[w], after[w]) for w in expected} == expected
    # nothing else moved: the router, the experts, the head
    assert {w: n for w, n in before.items() if w not in expected} == {
        w: n for w, n in after.items() if w not in expected
    }
    assert kernels_after == kernels_before
    assert any(name.endswith("bwd_tiled") for name in kernels_after)
    if family == "lfm2":
        assert kernels_after["short_conv_bwd"] == kernels_after[
            "short_conv_fwd"
        ]


# (model name, the cell's cut, the cell's sequence length, bytes a micro-batch
# of one row in bf16: the kernels' operands — q + k + v of an attention
# layer, B | C | u of a convolution layer —, what ``whole_mixer`` keeps
# besides — the stream after every mixer and, behind a q / k norm, q_proj's
# and k_proj's outputs)
PUBLISHED = {
    "smallthinker": (
        "smallthinker_21b_a3b", dict(num_hidden_layers=4, vocab_size=18992,
                                     expert_shard="0/8"), 16384,
        4 * 16384 * (28 + 2 * 4) * 128 * 2, 4 * 16384 * 2560 * 2,
    ),
    "sdar": (
        "sdar_30b_a3b", dict(num_hidden_layers=4, vocab_size=18992,
                             expert_shard="0/8"), 4096,
        4 * 2 * 4096 * (32 + 2 * 4) * 128 * 2,  # both streams' positions
        4 * 8192 * (4096 + 512 + 2048) * 2,
    ),
    "lfm2": (
        "lfm2_24b_a2b", dict(num_hidden_layers=5, vocab_size=8192,
                             expert_shard="0/8"), 4096,
        4 * 4096 * 3 * 2048 * 2 + 4096 * (32 + 2 * 8) * 64 * 2,
        5 * 4096 * 2048 * 2 + 4096 * (2048 + 512) * 2,
    ),
    # three sliding layers at 64 heads, two full ones at 48, 8 kv heads;
    # the stream after every mixer and every gate's logits
    "laguna": (
        "laguna_xs2_33b_a3b", dict(num_hidden_layers=5, vocab_size=12544,
                                   expert_shard="0/32"), 8192,
        8192 * (3 * (64 + 2 * 8) + 2 * (48 + 2 * 8)) * 128 * 2,
        5 * 8192 * 2048 * 2 + 8192 * (3 * 64 + 2 * 48) * 2,
    ),
}
# ... the sums ISSUE 46 and docs/observability.md state
MIXER_BYTES = {
    "smallthinker": 335_544_320, "sdar": 436_207_616, "lfm2": 104_857_600,
    "laguna": 172_490_752,
}


@functools.lru_cache(maxsize=None)  # ``kernel_outputs``: once a family
def _kept_bytes(family, remat_policy):
    """``remat.kept_bytes`` of ``family`` at the published widths and the
    benchmark cell's cut under ``remat_policy`` ("": the family's default),
    from ``jax.eval_shape`` (nothing allocated); with the policy's name."""
    name, cut, seq = PUBLISHED[family][:3]
    cfg, model = build_model(name, remat_policy, **cut)
    params = jax.eval_shape(
        lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
        jax.random.PRNGKey(0),
    )
    batch = jax.eval_shape(lambda: drop_collator_keys(
        next(model_family(cfg).synthetic_batches(cfg, 1, seq, 0))
    ))
    assert batch["input_ids"].shape == (1, seq)
    return cfg.remat_policy, stash_bytes(
        build_loss_fn(model), params, batch, jax.random.PRNGKey(0)
    )


def check_kept_bytes_is_the_shapes_arithmetic(family, policy):
    """``kernel_operands`` keeps exactly the kernels' operands more than
    ``kernel_outputs`` — 151 MB a layer in SmallThinker's cell —,
    ``whole_mixer``, the families' default, exactly the sums and the q / k
    norm's inputs more than that."""
    operands, mixer = PUBLISHED[family][3:]
    assert mixer == MIXER_BYTES[family]
    if policy == "whole_mixer":
        built, kept = _kept_bytes(family, "")  # the default IS this row
        operands += mixer
    else:
        built, kept = _kept_bytes(family, policy)
    assert built == policy
    _name, kept_outputs = _kept_bytes(family, "kernel_outputs")
    assert kept - kept_outputs == operands
    if family == "smallthinker":
        assert PUBLISHED[family][3] // 4 == 150_994_944  # "151 MB a layer"
    # the layer inputs and the kernels' outputs are in both readings
    assert kept_outputs > operands // 2
