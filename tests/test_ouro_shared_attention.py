"""Ouro's attention is ``decoder.GroupedQueryAttention`` (PR 45), as the
other three rotate-half decoders' is: ``ouro_tiny``'s parameter tree and its
dense-path loss and gradient are what the tree before the move gave (recorded
there, bit for bit: the class went, no leaf and no operation did); and with
``attention_impl="flash"`` every one of the four decoders hands the flash
kernels q / k / v that are the outputs of ONE ``optimization_barrier`` —
buffers of their own, so XLA:TPU cannot fold RoPE's last add + cast into each
consumer and relay its float32 pieces around every one (PERF.md section 6,
PR 41 and PR 45) — while the dense path traces no barrier."""
import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.models import lfm2_moe, ouro, sdar_moe, smallthinker
from dedloc_tpu.models.decoder import rope_tables

# the MODULE: ``dedloc_tpu.ops`` exports the function under the same name
flash_module = importlib.import_module("dedloc_tpu.ops.flash_attention")

# ``OuroConfig.tiny()`` in the parent tree (commit ffa1385: ``OuroAttention``
# a class of its own), paths as ``jax.tree_util.keystr`` gives them
PARENT_TREE = {
    "['embed_tokens']": (256, 32),
    "['lm_head']": (32, 256),
    "['model']['early_exit_gate']['bias']": (1,),
    "['model']['early_exit_gate']['kernel']": (32, 1),
    "['model']['layers']['block']['down_proj']['kernel']": (2, 48, 32),
    "['model']['layers']['block']['gate_proj']['kernel']": (2, 32, 48),
    "['model']['layers']['block']['input_layernorm']['weight']": (2, 32),
    "['model']['layers']['block']['input_layernorm_2']['weight']": (2, 32),
    "['model']['layers']['block']['post_attention_layernorm']['weight']":
        (2, 32),
    "['model']['layers']['block']['post_attention_layernorm_2']['weight']":
        (2, 32),
    "['model']['layers']['block']['self_attn']['k_proj']['kernel']":
        (2, 32, 32),
    "['model']['layers']['block']['self_attn']['o_proj']['kernel']":
        (2, 32, 32),
    "['model']['layers']['block']['self_attn']['q_proj']['kernel']":
        (2, 32, 32),
    "['model']['layers']['block']['self_attn']['v_proj']['kernel']":
        (2, 32, 32),
    "['model']['layers']['block']['up_proj']['kernel']": (2, 32, 48),
    "['model']['norm']['weight']": (32,),
}
# the parent's ``ouro_loss`` on ``_tiny_batch`` (bf16 compute, dense
# attention, CPU): the loss as ``float.hex`` and the sha256 of the gradient's
# leaves in tree order. The same bytes on one thread and on eight, with one
# host device and with eight
PARENT_LOSS = "0x1.6061ae0000000p+2"
PARENT_GRAD_SHA256 = (
    "2ceb0dee27fa66ed3a222a7c76643781321e0618cc6369e7ec9b511c1839d1ca"
)


def _tiny_batch():
    cfg = ouro.OuroConfig.tiny()
    model = ouro.OuroForCausalLM(cfg)
    rows = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 65)
    ).astype(np.int32)
    batch = {"input_ids": jnp.asarray(rows[:, :-1]),
             "labels": jnp.asarray(rows[:, 1:])}
    params = model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    return model, params, batch


def test_tiny_parameter_tree_is_the_parents():
    _, params, _ = _tiny_batch()
    tree = {
        jax.tree_util.keystr(path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }
    assert tree == PARENT_TREE
    assert {leaf.dtype for leaf in jax.tree.leaves(params)} == {
        jnp.dtype(jnp.float32)
    }


def test_dense_loss_and_gradient_are_the_parents_bits():
    model, params, batch = _tiny_batch()
    (loss, _), grads = jax.value_and_grad(
        lambda p: ouro.ouro_loss(model, p, batch), has_aux=True
    )(params)
    assert float(loss).hex() == PARENT_LOSS
    digest = hashlib.sha256()
    for leaf in jax.tree.leaves(grads):
        digest.update(np.asarray(leaf).tobytes())
    assert digest.hexdigest() == PARENT_GRAD_SHA256


# one attention layer of each rotate-half decoder, as its model builds it
LAYERS = {
    "ouro": lambda impl: ouro.OuroLayer(
        ouro.OuroConfig.tiny(attention_impl=impl)
    ),
    "smallthinker": lambda impl: smallthinker.DecoderLayer(
        smallthinker.SmallThinkerConfig.tiny(attention_impl=impl),
        rotated=True, banded=True,
    ),
    "sdar": lambda impl: sdar_moe.DecoderLayer(
        sdar_moe.SdarMoeConfig.tiny(attention_impl=impl)
    ),
    "lfm2": lambda impl: lfm2_moe.DecoderLayer(
        lfm2_moe.Lfm2MoeConfig.tiny(attention_impl=impl),
        mixer=lfm2_moe.ATTENTION, sparse=False,
    ),
}


def _equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(inner)


def _layer_equations(layer):
    """The equations of one layer's forward (its ``init``: parameters made,
    the layer applied) at [1, 32, hidden]."""
    cfg = layer.cfg
    hidden = jnp.zeros((1, 32, cfg.hidden_size), cfg.dtype)
    rope = rope_tables(32, cfg.head_dim, cfg.rope_theta)
    traced = jax.make_jaxpr(
        lambda h: layer.init_with_output(jax.random.PRNGKey(0), h, rope)[0]
    )(hidden)
    return list(_equations(traced.jaxpr))


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_flash_operands_are_one_barriers_outputs(name, monkeypatch):
    calls = []

    @jax.jit
    def recording_flash_stub(q, k, v):  # a named equation over q / k / v
        return jnp.zeros(q.shape[:3] + v.shape[3:], q.dtype)

    def flash_attention(q, k, v, **kwargs):
        calls.append((q.shape, k.shape, v.shape, q.dtype, k.dtype, kwargs))
        return recording_flash_stub(q, k, v)

    monkeypatch.setattr(flash_module, "flash_attention", flash_attention)
    layer = LAYERS[name]("flash")
    equations = _layer_equations(layer)
    cfg = layer.cfg
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    (q_shape, k_shape, v_shape, q_dtype, k_dtype, _), = calls
    assert q_shape == (1, 32, heads, cfg.head_dim)
    assert k_shape == v_shape == (1, 32, kv, cfg.head_dim)
    # RoPE works in float32; what the kernels read is the compute dtype
    assert q_dtype == k_dtype == cfg.dtype
    stub, = (
        e for e in equations
        if e.params.get("name") == "recording_flash_stub"
    )
    barrier, = (
        e for e in equations if e.primitive.name == "optimization_barrier"
    )
    assert list(stub.invars) == list(barrier.outvars)


@pytest.mark.parametrize("name", sorted(LAYERS))
def test_dense_path_traces_no_barrier(name):
    equations = _layer_equations(LAYERS[name]("dense"))
    assert equations
    assert not [
        e for e in equations if e.primitive.name == "optimization_barrier"
    ]
