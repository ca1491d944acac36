"""The tiled backward in ONE sweep (``flash_*_bwd_tiled``): dq, dk and dv
from one score / probability tile on the forward's walk, dk / dv of a kv
block held for the whole sequence in VMEM. The walks the two-kernel form
never took, in interpreter mode against a dense masked float32 attention —
a kv block that several query programs share (its accumulators zeroed at the
first program's first step, written at the last one's last), a group of
seven as one program, a band whose sweep starts past key tile 0, a selection
with an empty tile between two computed ones, the block rule's noisy
diagonal run — and the scoped VMEM the call asks for at the eight cells'
shapes, with the refusal above it."""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_flash_block_diffusion import _dense, visible as _block_rule

from dedloc_tpu.ops.flash_attention import flash_attention

fa = importlib.import_module("dedloc_tpu.ops.flash_attention")


def _causal(seq, band=None):
    i = np.arange(seq)
    seen = i[None, :] <= i[:, None]
    return seen if band is None else seen & (i[:, None] - i[None, :] < band)


def _selection_with_a_hole(seq, tile):
    """A random selection over the triangle whose query tile 3 holds
    nothing in key tile 1, between key tiles 0 and 2 that it does."""
    rng = np.random.default_rng(5)
    chosen = (rng.random((seq, seq)) < 0.3) & _causal(seq)
    chosen[np.arange(seq), np.arange(seq)] = True
    chosen[3 * tile:4 * tile, tile:2 * tile] = False
    return chosen


# name -> (heads, kv heads, head width, S, tile, the call's mask arguments,
# what a query sees, the backward's grid)
CASES = {
    # two heads a program (512 x 512 tiles, the cells' geometry): the four
    # query programs of a group of eight meet in ONE kv head's dk / dv
    "a_kv_block_shared_by_four_programs": (
        8, 1, 128, 1024, 512, dict(causal=True), _causal(1024),
        (1, 1, 4, 2, 2),
    ),
    "a_group_of_seven_is_one_program": (
        7, 1, 128, 96, 32, dict(causal=True), _causal(96), (1, 1, 1, 3, 3),
    ),
    # query tile 3's first key is 96 - 39 = 57: its sweep starts at key
    # tile 1, and its rows of dk / dv are tile 1's, not step 0's
    "a_band_that_starts_past_key_tile_0": (
        4, 2, 128, 128, 32, dict(causal=True, band=40), _causal(128, 40),
        (1, 1, 1, 4, 3),
    ),
    "a_selection_with_an_empty_tile_between_two": (
        8, 1, 128, 128, 32,
        dict(selection=_selection_with_a_hole(128, 32)),
        _selection_with_a_hole(128, 32), (1, 1, 1, 4, 4),
    ),
    # a noisy query tile walks the clean tiles before it, THEN its own
    # noisy tile: the second run's rows of dk / dv lie before the first's
    "the_block_rules_noisy_diagonal_run": (
        8, 2, 64, 128, 32, dict(block_diffusion=4), _block_rule(64, 4),
        (1, 1, 1, 4, 3),
    ),
}


def _grid_of(call, *operands):
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and eqn.params[
                "name"
            ].endswith("bwd_tiled"):
                found.append(eqn.params["grid_mapping"].grid)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(jax.grad(
        lambda *x: jnp.sum(call(*x)), argnums=(0, 1, 2)
    ))(*operands).jaxpr)
    (grid,) = found  # ONE backward kernel a call
    return grid


@pytest.mark.parametrize("case", CASES.values(), ids=CASES.keys())
def test_one_sweep_against_dense(case):
    h, kv, d, seq, tile, kwargs, seen, grid = case
    rng = np.random.default_rng(1)
    q, k, v, do = (
        jnp.asarray(rng.standard_normal((1, seq, n, d)), jnp.float32)
        for n in (h, kv, kv, h)
    )
    kwargs = dict(kwargs, block_q=tile, block_k=tile)
    selected = "selection" in kwargs
    if selected:
        kwargs["selection"] = jnp.asarray(kwargs["selection"], jnp.int8)[None]
        assert not int(fa.selection_tile_flags(
            kwargs["selection"], tile, tile
        )[0, 3, 1])  # the hole is a tile the flags skip

    def call(q, k, v):
        out = flash_attention(q, k, v, **kwargs)
        return out[0] if selected else out

    assert _grid_of(call, q, k, v) == grid
    out, vjp = jax.vjp(call, q, k, v)
    want, want_vjp = jax.vjp(
        lambda *x: _dense(*x, jnp.asarray(seen)), q, k, v
    )
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(vjp(do), want_vjp(do), ("dq", "dk", "dv")):
        np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4,
                                   err_msg=name)


# the eight decoder cells' attention calls: (S, heads, kv heads, q/k width,
# v width, selected) -> MiB of float32 accumulators (dk + dv of a kv block)
CELLS = {
    "ouro": ((4096, 16, 16, 128, 128, False), 8),
    "kanana2": ((4096, 32, 32, 192, 128, False), 10),
    "kimi": ((8192, 32, 32, 192, 128, False), 20),
    "lfm2": ((4096, 32, 8, 64, 64, False), 4),
    "smallthinker": ((16384, 28, 4, 128, 128, False), 16),
    "laguna_band": ((8192, 64, 8, 128, 128, False), 8),
    "laguna_full": ((8192, 48, 8, 128, 128, False), 8),
    "sdar": ((8192, 32, 4, 128, 128, False), 8),
    "keye": ((16384, 32, 4, 128, 128, True), 16),
}


def _operand_shapes(seq, h, kv, d):
    return (jax.ShapeDtypeStruct((1, seq, h * d), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, seq, kv * d), jnp.bfloat16))


@pytest.mark.parametrize("cell", CELLS.values(), ids=CELLS.keys())
def test_the_cells_backward_fits_the_vmem_it_asks_for(cell):
    """A cell's call asks for more than what it holds for the whole
    sequence (the accumulators and the output blocks' two buffers) plus a
    step's blocks, and for less than a v5e core has."""
    (seq, h, kv, d, dv, selected), accumulators_mb = cell
    q, k = _operand_shapes(seq, h, kv, d)
    kvb = fa._bwd_geometry(q, k, d, dv, 512, 512)[-1]
    accumulators, outputs = fa._bwd_resident(seq, kvb, d, dv, 2)
    assert accumulators == accumulators_mb * 2**20 == outputs
    asked = fa._bwd_vmem(q, k, d, dv, 512, 512, selected).vmem_limit_bytes
    assert accumulators + outputs + 8 * 2**20 < asked <= fa._VMEM_CEILING
    assert asked <= 80 * 2**20  # the most, SmallThinker's group of seven


def test_a_sequence_whose_dk_and_dv_do_not_fit_is_refused():
    """The sequence is bounded by VMEM in the backward: twice the cells'
    longest still fits at two heads a program, four times it does not —
    nor three times under a group of seven — and the error says what is
    held; a test model's call carries no compiler parameters."""
    assert fa._bwd_vmem(*_operand_shapes(32768, 32, 4, 128), 128, 128, 512,
                        512) is not None
    for seq, h in ((65536, 32), (49152, 28)):
        with pytest.raises(ValueError, match="whole sequence"):
            fa._bwd_vmem(*_operand_shapes(seq, h, 4, 128), 128, 128, 512,
                         512)
    assert fa._bwd_vmem(*_operand_shapes(128, 4, 4, 64), 64, 64, 32,
                        32) is None
