"""``ouro_tiny`` through the model against the plain reference
(``benchmark/reference/ouro.py``: float32, matmul precision 'highest', no
kernels, no remat, whole logits) on seeded random weights: loss, per-pass
logits, exit distribution, the whole gradient and its worst leaf, with dense
and with flash attention; the chunked head + loss against the unchunked one;
the exit distribution summing to 1 and the entropy term's sign; and a
reference one pass short, or with the final norm outside the loop, failing
by orders of magnitude; and what the layer keeps under remat (the flash
kernel's outputs): the same bits as replaying everything, one forward kernel
call in the gradient's jaxpr instead of two."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import decoder_cases as cases
from benchmark.reference import ouro as reference
from dedloc_tpu.models.decoder import chunked_cross_entropy
from dedloc_tpu.models.ouro import (
    OuroConfig,
    OuroForCausalLM,
    exit_distribution,
    gated_loss,
    ouro_logits,
    ouro_loss,
    ouro_train_tflops_per_sample,
)
from dedloc_tpu.models.remat import remat_policy_object

# float32 on both sides: what is left is the order of the arithmetic
LOSS_TOL, GRAD_TOL, LEAF_TOL = 2e-6, 2e-5, 1e-4


def _setup(impl, **overrides):
    cfg = OuroConfig.tiny(
        dtype=jnp.float32, attention_impl=impl, attention_block_size=32,
        **overrides,
    )
    model = OuroForCausalLM(cfg)
    batch = cases.token_batch(cfg, 64)
    # away from the initialiser's symmetry: norms off 1, gate bias off 0
    params = cases.perturbed(
        model.init(jax.random.PRNGKey(1), batch["input_ids"])["params"]
    )
    return cfg, model, params, batch


def _reference_kwargs(cfg, **changes):
    kwargs = dict(
        num_heads=cfg.num_attention_heads, eps=cfg.rms_norm_eps,
        theta=cfg.rope_theta, passes=cfg.total_ut_steps,
        beta=cfg.exit_entropy_beta,
    )
    kwargs.update(changes)
    return kwargs


def _compare(grads, ref_grads):
    """(whole-gradient relative L2, worst leaf's)."""
    a, b = jax.tree.leaves(grads), jax.tree.leaves(ref_grads)
    whole = np.sqrt(sum(float(jnp.sum((x - y) ** 2)) for x, y in zip(a, b)))
    whole /= np.sqrt(sum(float(jnp.sum(y ** 2)) for y in b))
    worst = max(
        float(jnp.linalg.norm(x - y) / jnp.linalg.norm(y)) for x, y in zip(a, b)
    )
    return whole, worst


@functools.lru_cache(maxsize=None)
def _role(impl):
    """The model's own side, once a process: the different-function cases
    compile their reference alone."""
    cfg, model, params, batch = _setup(impl)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: ouro_loss(model, p, batch), has_aux=True
        )(params)
        hiddens, _gates = model.apply({"params": params}, batch["input_ids"])
        logits = ouro_logits(params, hiddens, cfg)
    return cfg, params, batch, loss, metrics, grads, logits


def _role_and_reference(impl, **reference_changes):
    cfg, params, batch, loss, metrics, grads, logits = _role(impl)
    with jax.default_matmul_precision("highest"):
        kwargs = _reference_kwargs(cfg, **reference_changes)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.loss_fn(p, batch, **kwargs)
        )(params)
        out = reference.forward(params, batch, **kwargs)
    return cfg, loss, metrics, grads, logits, ref_loss, ref_grads, out


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_model_matches_reference(impl):
    cfg, loss, metrics, grads, logits, ref_loss, ref_grads, out = (
        _role_and_reference(impl)
    )
    assert abs(float(loss) - float(ref_loss)) <= LOSS_TOL * abs(float(ref_loss))
    np.testing.assert_allclose(logits, out["logits"], atol=2e-5)
    assert logits.shape == (cfg.total_ut_steps, 2, 64, cfg.vocab_size)
    np.testing.assert_allclose(
        metrics["lm.exit_prob"], jnp.mean(out["p"], axis=(1, 2)), atol=1e-6
    )
    np.testing.assert_allclose(
        metrics["lm.loss"], jnp.mean(out["ce"], axis=(1, 2)), rtol=1e-5
    )
    whole, worst = _compare(grads, ref_grads)
    assert whole <= GRAD_TOL and worst <= LEAF_TOL, (whole, worst)
    # every leaf is trained: the gate, the final norm, both embeddings
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree.leaves(grads))


@pytest.mark.parametrize("change", [
    {"passes": 2},  # one pass short
    {"final_norm_inside": False},  # the final norm applied outside the loop
])
def test_a_different_function_fails_by_orders_of_magnitude(change):
    _cfg, loss, _m, grads, _l, ref_loss, ref_grads, _out = (
        _role_and_reference("dense", **change)
    )
    whole, worst = _compare(grads, ref_grads)
    # read: gradient 0.26 / 0.38 relative L2, worst leaf 1.4 / 2.3, loss
    # 4.2e-3 / 1.5e-3 relative (random weights keep every pass near ln V)
    assert whole > 1e3 * GRAD_TOL and worst > 1e3 * LEAF_TOL, (whole, worst)
    assert (
        abs(float(loss) - float(ref_loss)) > 1e2 * LOSS_TOL * abs(float(ref_loss))
    )


@pytest.mark.parametrize("chunk", [16, 64, 128])
def test_chunked_head_matches_unchunked(chunk):
    """[T, N] cross-entropy and its gradients, one (pass, chunk) of logits at
    a time under remat, against the whole [T, N, V] logits at once."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    hiddens = jax.random.normal(keys[0], (3, 128, 32))
    head = jax.random.normal(keys[1], (32, 256)) * 0.2
    labels = jax.random.randint(keys[2], (128,), 0, 256)

    def whole(h, w):
        log_probs = jax.nn.log_softmax(jnp.einsum("tnh,hv->tnv", h, w), -1)
        return -jnp.take_along_axis(
            log_probs, jnp.broadcast_to(labels, (3, 128))[..., None], -1
        )[..., 0]

    weights = jnp.arange(3 * 128, dtype=jnp.float32).reshape(3, 128) / 100
    with jax.default_matmul_precision("highest"):
        got = chunked_cross_entropy(hiddens, head, labels, chunk)
        np.testing.assert_allclose(got, whole(hiddens, head), atol=1e-5)
        g_got = jax.grad(
            lambda h, w: jnp.sum(
                chunked_cross_entropy(h, w, labels, chunk) * weights
            ), (0, 1)
        )(hiddens, head)
        g_want = jax.grad(
            lambda h, w: jnp.sum(whole(h, w) * weights), (0, 1)
        )(hiddens, head)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_chunk_must_divide_the_tokens():
    with pytest.raises(ValueError, match="must divide"):
        chunked_cross_entropy(
            jnp.zeros((2, 48, 8)), jnp.zeros((8, 16)),
            jnp.zeros((48,), jnp.int32), 32,
        )


def test_exit_distribution_sums_to_one_and_entropy_is_a_bonus():
    gates = jax.random.normal(jax.random.PRNGKey(4), (4, 50)) * 3.0
    p, log_p = exit_distribution(gates)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    assert bool(jnp.all(p > 0))
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    # by hand: lambda = 1/2 everywhere gives 1/2, 1/4, 1/8 and the rest, 1/8
    p_half, _ = exit_distribution(jnp.zeros((4, 1)))
    np.testing.assert_allclose(p_half[:, 0], [0.5, 0.25, 0.125, 0.125])
    # saturated gates neither overflow nor produce NaN
    p_sat, log_sat = exit_distribution(jnp.array([[80.0], [-80.0], [0.0]]))
    assert bool(jnp.all(jnp.isfinite(log_sat))) and float(p_sat[0, 0]) == 1.0
    # the entropy term LOWERS the loss (a bonus for spreading the exits):
    # equal CE at every pass leaves only -beta * H(p)
    ce = jnp.ones((4, 50))
    loss0, _ = gated_loss(ce, gates, beta=0.0)
    loss1, metrics = gated_loss(ce, gates, beta=0.05)
    assert float(loss0) == pytest.approx(1.0, abs=1e-6)
    assert float(metrics["exit_entropy"]) > 0
    assert float(loss1) == pytest.approx(
        1.0 - 0.05 * float(metrics["exit_entropy"]), abs=1e-6
    )
    # uniform p has the largest entropy, log 4
    uniform, _ = exit_distribution(
        jnp.log(jnp.array([[1 / 3], [1 / 2], [1.0], [0.0]]))
    )
    np.testing.assert_allclose(uniform[:, 0], 0.25, atol=1e-6)


def test_published_preset_and_flop_model():
    cfg = OuroConfig.named("ouro_2p6b")()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.vocab_size, cfg.num_hidden_layers,
            cfg.total_ut_steps) == (2048, 16, 128, 5632, 49152, 48, 4)
    with pytest.raises(ValueError, match="unknown model_size"):
        OuroConfig.named("ouro_7b")
    # by hand at 3 layers, S=4,096: per token and layer 2*4*2048^2 (q k v o)
    # + 2*3*2048*5632 (SwiGLU) + 2*2*2048*2048.5 (the causal triangle);
    # per pass the head 2*2048*49152; four passes; backward twice the forward
    layer = 2 * 4 * 2048 ** 2 + 2 * 3 * 2048 * 5632 + 2 * 2 * 2048 * 2048.5
    want = 3 * 4 * (3 * layer + 2 * 2048 * 49152) * 4096 / 1e12
    small = OuroConfig.ouro_2p6b(num_hidden_layers=3)
    assert ouro_train_tflops_per_sample(small, 4096) == pytest.approx(want)
    assert 27.0 < want < 28.0  # TFLOPs a row


# ------------------------------------- what the layer keeps under remat


def _flash_value_and_grad(remat_policy):
    """(function, its argument): loss and gradients of ``ouro_tiny`` with
    the causal flash kernels under one layer policy."""
    _cfg, model, params, batch = _setup("flash", remat_policy=remat_policy)
    return jax.value_and_grad(lambda p: ouro_loss(model, p, batch)[0]), params


def _pallas_calls(jaxpr_text, name):
    """Call sites of the Pallas kernel ``name`` in a printed jaxpr (its
    pallas_call equations carry the names, nested jaxprs are printed in
    place: scan bodies, remat's replay, the custom VJP's halves)."""
    return len(re.findall(rf"\bname={name}\b", jaxpr_text))


def test_default_policy_keeps_the_kernel_outputs_and_the_same_bits():
    """The saved ``out`` / ``lse`` are the bits the replay would have
    produced: the loss and EVERY gradient leaf agree exactly."""
    fn, params = _flash_value_and_grad(OuroConfig().remat_policy)
    ref_fn, ref_params = _flash_value_and_grad("nothing")
    loss, grads = fn(params)
    ref_loss, ref_grads = ref_fn(ref_params)
    assert float(loss) == float(ref_loss)
    jax.tree_util.tree_map_with_path(  # raises on a different tree, too
        lambda path, leaf, ref_leaf: np.testing.assert_array_equal(
            leaf, ref_leaf, err_msg=jax.tree_util.keystr(path)
        ),
        grads, ref_grads,
    )


@pytest.mark.parametrize("policy,forward_calls", [
    ("kernel_outputs", 1),  # the forward scan's; the backward reads its stash
    ("nothing", 2),  # + the one in the backward's replay of the layer
])
def test_forward_kernel_call_sites_in_the_gradient(policy, forward_calls):
    """The engagement count with no chip: a scanned layer body is ONE call
    site per appearance, so a remat replay of the kernel is a second one."""
    fn, params = _flash_value_and_grad(policy)
    jaxpr = str(jax.make_jaxpr(fn)(params))  # traced, not run
    assert _pallas_calls(jaxpr, "flash_causal_fwd") == forward_calls
    # the one-sweep backward runs once under either policy
    assert _pallas_calls(jaxpr, "flash_causal_bwd_tiled") == 1
    assert "bwd_dq" not in jaxpr and "bwd_dkv" not in jaxpr


def test_default_policy_is_a_table_entry_and_unknown_names_raise():
    assert OuroConfig().remat_policy == "kernel_outputs"
    assert callable(remat_policy_object("kernel_outputs"))
    assert callable(remat_policy_object("nothing"))  # stays in the table
    with pytest.raises(ValueError, match="unknown remat_policy"):
        remat_policy_object("kernel_output")
