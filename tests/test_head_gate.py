"""The per-head output gate's kernel pair (``ops/head_gate.py``), in
interpreter mode, held to XLA's expression — the one the ``"dense"`` path
and every head width the kernels do not serve keep."""
import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.models.decoder import (
    GroupedQueryAttention,
    Visibility,
    head_gate,
    rope_tables,
)
from dedloc_tpu.models.remat import remat_policy_object
from dedloc_tpu.ops.head_gate import ROWS, gate_heads, gate_heads_xla

D = 128


def _operands(batch, seq, heads, width=D, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    ctx, dy = (
        jax.random.normal(k, (batch, seq, heads * width), jnp.float32)
        .astype(jnp.bfloat16) for k in keys[:2]
    )
    gate = jax.nn.sigmoid(jax.random.normal(keys[2], (batch, seq, heads)))
    return ctx, gate, dy


def _kernel_sites(fn, *args):
    """Call sites of each Pallas kernel in ``fn``'s jaxpr, by name."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = eqn.params["name"]
                found[name] = found.get(name, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("seq", [ROWS, ROWS + 72])  # whole blocks; a tail
@pytest.mark.parametrize("heads", [6, 8, 64])
def test_the_kernels_against_xlas_expression(heads, seq, batch):
    ctx, gate, dy = _operands(batch, seq, heads)
    out, vjp = jax.vjp(gate_heads, ctx, gate)
    ref, ref_vjp = jax.vjp(gate_heads_xla, ctx, gate)
    assert out.dtype == ctx.dtype and out.shape == ctx.shape
    np.testing.assert_array_equal(out, ref)  # to the bit
    (d_ctx, d_gate), (ref_d_ctx, ref_d_gate) = vjp(dy), ref_vjp(dy)
    assert d_ctx.dtype == ctx.dtype and d_gate.dtype == jnp.float32
    np.testing.assert_array_equal(d_ctx, ref_d_ctx)
    assert float(
        jnp.linalg.norm(d_gate - ref_d_gate) / jnp.linalg.norm(ref_d_gate)
    ) <= 1e-6
    assert _kernel_sites(
        lambda c, g: jax.vjp(gate_heads, c, g)[1](dy), ctx, gate
    ) == {"head_gate_fwd": 1, "head_gate_bwd": 1}


def test_a_head_that_is_no_whole_lane_tile_keeps_xlas_expression():
    ctx, gate, dy = _operands(2, 32, 4, width=64)
    assert _kernel_sites(jax.grad(
        lambda c, g: jnp.sum(gate_heads(c, g).astype(jnp.float32)), (0, 1)
    ), ctx, gate) == {}
    out, vjp = jax.vjp(gate_heads, ctx, gate)
    ref, ref_vjp = jax.vjp(gate_heads_xla, ctx, gate)
    np.testing.assert_array_equal(out, ref)
    for got, want in zip(vjp(dy), ref_vjp(dy)):
        np.testing.assert_array_equal(got, want)


class _GatedMixer(nn.Module):
    """A projection standing for the flash call, the gate, ``o_proj``."""

    heads: int = 2

    @nn.compact
    def __call__(self, x):
        wide = nn.Dense(self.heads * D, use_bias=False, dtype=jnp.bfloat16)
        gate = jax.nn.sigmoid(
            nn.Dense(self.heads, use_bias=False)(x).astype(jnp.float32)
        )
        return nn.Dense(x.shape[-1], use_bias=False, dtype=jnp.bfloat16)(
            gate_heads(wide(x), gate)
        )


@pytest.mark.parametrize(
    "policy", ["kernel_outputs", "kernel_operands", "whole_mixer"]
)
def test_grad_through_remat_gives_the_same_bits(policy):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 24, 32), jnp.bfloat16)
    plain = _GatedMixer()
    params = plain.init(jax.random.PRNGKey(1), x)
    remat = nn.remat(_GatedMixer, policy=remat_policy_object(policy))()

    def grads(module):
        return jax.grad(lambda p, x: jnp.sum(
            module.apply(p, x).astype(jnp.float32) ** 2
        ), (0, 1))

    want, got = grads(plain)(params, x), grads(remat)(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
    # the gated context is kept by no rung (``remat.REPLAYED_KERNELS``): the
    # backward's replay makes it again, one more site of the forward kernel
    assert _kernel_sites(grads(remat), params, x) == {
        "head_gate_fwd": 2, "head_gate_bwd": 1,
    }
    assert _kernel_sites(grads(plain), params, x) == {
        "head_gate_fwd": 1, "head_gate_bwd": 1,
    }


@dataclasses.dataclass(frozen=True)
class _Cfg:
    hidden_size: int = 32
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = D
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: object = jnp.bfloat16
    attention_impl: str = "flash"
    attention_block_size: int = 16
    mesh: object = None


class _Attention(nn.Module):
    cfg: _Cfg
    gated: bool

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = head_gate(cfg, x, cfg.num_attention_heads)
        return GroupedQueryAttention(cfg, Visibility(causal=True))(
            x, rope_tables(x.shape[1], D, 10000.0),
            gate if self.gated else None,
        )


@pytest.mark.parametrize("impl, gated, devices, sites", [
    ("flash", True, 0, 1), ("flash", False, 0, 0), ("dense", True, 0, 0),
    ("flash", True, 2, 0),
])
def test_the_attention_takes_the_kernel_behind_the_flash_call(
    impl, gated, devices, sites
):
    """What the code observes in its input decides: a gate was passed, the
    implementation is ``flash``, the program is one device's (``devices``:
    a data mesh of that many, whose gate stays XLA's)."""
    mesh = jax.sharding.Mesh(
        np.array(jax.devices()[:devices]), ("data",)
    ) if devices else None
    mixer = _Attention(_Cfg(attention_impl=impl, mesh=mesh), gated)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32), jnp.bfloat16)
    params = mixer.init(jax.random.PRNGKey(1), x)
    assert _kernel_sites(mixer.apply, params, x).get(
        "head_gate_fwd", 0
    ) == sites
    # one function whichever way it is computed
    other = _Attention(
        _Cfg(attention_impl="dense" if impl == "flash" else "flash"), gated
    )
    np.testing.assert_allclose(
        np.asarray(mixer.apply(params, x), np.float32),
        np.asarray(other.apply(params, x), np.float32), atol=2e-2, rtol=2e-2,
    )
