"""The tiled backward in ONE sweep (``flash_*_bwd_tiled``): dq, dk and dv
from one score / probability tile on the forward's walk, dk / dv of a kv
block held for the whole sequence in VMEM. The walks the two-kernel form
never took, in interpreter mode against a dense masked float32 attention —
a kv block that several query programs share (its accumulators zeroed at the
first program's first step, written at the last one's last), a group of
seven or of eight as one program, a band whose sweep starts past key tile 0,
a selection with an empty tile between two computed ones, the block rule's
noisy diagonal run — and the heads a program that a call takes from the VMEM
it asks for (``_heads_a_program``): at the ten cells' shapes, down the
plans as the sequence grows, and the refusal below the last."""
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
# what a query sees, the backward's grid (B, kv blocks, a kv block's query
# programs, query tiles, key steps), MiB left under the ceiling for a
# program of more than one column block: None, the module's own)
CASES = {
    # the cells' geometry, 512 x 512 tiles: a group of eight is ONE program
    # (two heads a program and four ``members`` until PR 58)
    "a_group_of_eight_is_one_program": (
        8, 1, 128, 1024, 512, dict(causal=True), _causal(1024),
        (1, 1, 1, 2, 2), None,
    ),
    # sixteen heads over one kv head: two programs of eight meet in ONE kv
    # head's dk / dv (zeroed at the first's first step, written at the
    # second's last)
    "a_kv_block_shared_by_two_programs_of_eight": (
        16, 1, 128, 96, 32, dict(causal=True), _causal(96), (1, 1, 2, 3, 3),
        None,
    ),
    "a_group_of_seven_is_one_program": (
        7, 1, 128, 96, 32, dict(causal=True), _causal(96), (1, 1, 1, 3, 3),
        None,
    ),
    # as many kv heads as heads: dk / dv of a program's OWN heads are
    # resident, so eight heads' do not fit where four heads' do (8 MiB of
    # room: 8.77 MiB asked at eight, 6.38 at four) — two kv blocks of four
    "an_equal_count_call_at_four_heads_a_program": (
        8, 8, 128, 128, 32, dict(causal=True), _causal(128), (1, 2, 1, 4, 4),
        8,
    ),
    # two heads share a lane tile: four heads are two column blocks, over
    # the one column block of their two kv heads
    "d64_with_two_column_blocks_a_program": (
        4, 2, 64, 128, 32, dict(causal=True), _causal(128), (1, 1, 1, 4, 4),
        None,
    ),
    # query tile 3's first key is 96 - 39 = 57: its sweep starts at key
    # tile 1, and its rows of dk / dv are tile 1's, not step 0's
    "a_band_that_starts_past_key_tile_0": (
        4, 2, 128, 128, 32, dict(causal=True, band=40), _causal(128, 40),
        (1, 1, 1, 4, 3), None,
    ),
    "a_selection_with_an_empty_tile_between_two": (
        8, 1, 128, 128, 32,
        dict(selection=_selection_with_a_hole(128, 32)),
        _selection_with_a_hole(128, 32), (1, 1, 1, 4, 4), None,
    ),
    # a noisy query tile walks the clean tiles before it, THEN its own
    # noisy tile: the second run's rows of dk / dv lie before the first's
    "the_block_rules_noisy_diagonal_run": (
        8, 2, 64, 128, 32, dict(block_diffusion=4), _block_rule(64, 4),
        (1, 1, 1, 4, 3), None,
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
def test_one_sweep_against_dense(case, monkeypatch):
    h, kv, d, seq, tile, kwargs, seen, grid, room_mb = case
    if room_mb is not None:
        monkeypatch.setattr(
            fa, "_VMEM_MARGIN", fa._VMEM_CEILING - room_mb * 2**20
        )
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


# the ten decoder cells' attention calls: (S, heads, kv heads, q/k width,
# v width, selected) -> (heads a program forward, backward, MiB of float32
# accumulators: dk + dv of the backward's kv block)
CELLS = {
    "ouro": ((4096, 16, 16, 128, 128, False), (8, 4, 16)),
    "kanana2": ((4096, 32, 32, 192, 128, False), (8, 4, 20)),
    "kimi": ((8192, 32, 32, 192, 128, False), (8, 2, 20)),
    "lfm2": ((4096, 32, 8, 64, 64, False), (8, 8, 4)),
    "smallthinker": ((16384, 28, 4, 128, 128, False), (7, 7, 16)),
    "laguna_band": ((8192, 64, 8, 128, 128, False), (8, 8, 8)),
    "laguna_full": ((8192, 48, 8, 128, 128, False), (6, 6, 8)),
    "sdar": ((8192, 32, 4, 128, 128, False), (8, 8, 8)),
    "keye": ((16384, 32, 4, 128, 128, True), (8, 8, 16)),
    "nemotron": ((8192, 16, 1, 128, 128, False), (8, 8, 8)),
}


def _operand_shapes(seq, h, kv, d):
    return (jax.ShapeDtypeStruct((1, seq, h * d), jnp.bfloat16),
            jax.ShapeDtypeStruct((1, seq, kv * d), jnp.bfloat16))


@pytest.mark.parametrize("cell", CELLS.values(), ids=CELLS.keys())
def test_the_cells_backward_fits_the_vmem_it_asks_for(cell):
    """A cell's call takes a whole kv group a program where the group is
    eight or fewer and what fits where dk / dv grow with the heads, asks for
    more than what it holds for the whole sequence (the accumulators and the
    output blocks' two buffers) plus a step's blocks, and for less than a
    v5e core has."""
    (seq, h, kv, d, dv, selected), (fwd_heads, heads, accumulators_mb) = cell
    q, k = _operand_shapes(seq, h, kv, d)
    assert fa._fwd_geometry(q, k, d, dv, 512, 512, selected)[4] == fwd_heads
    geometry = fa._bwd_geometry(q, k, d, dv, 512, 512, selected)
    assert geometry[4] == heads
    accumulators, outputs = fa._bwd_resident(seq, geometry[-1], d, dv, 2)
    assert accumulators == accumulators_mb * 2**20 == outputs
    asked = fa._bwd_vmem(q, k, d, dv, 512, 512, selected).vmem_limit_bytes
    assert accumulators + outputs + 8 * 2**20 < asked <= fa._VMEM_CEILING
    assert asked <= 84 * 2**20  # the most, Keye's group of eight


@pytest.mark.parametrize(
    "seq,heads,asked_mb",
    [(16384, 8, 82.5), (32768, 4, 91.5), (40960, 2, 96.0),
     (49152, 1, 106.25)],
)
def test_the_count_degrades_before_the_refusal(seq, heads, asked_mb):
    """The sequence is bounded by VMEM in the backward, and the heads a
    program give way first: a group of eight over one kv head of 128 is one
    program at the cells' longest, four heads at twice that, then two, then
    ONE column block — which may ask up to the ceiling."""
    q, k = _operand_shapes(seq, 32, 4, 128)
    assert fa._bwd_geometry(q, k, 128, 128, 512, 512)[4] == heads
    asked = fa._bwd_vmem(q, k, 128, 128, 512, 512).vmem_limit_bytes
    assert asked == asked_mb * 2**20 <= fa._VMEM_CEILING


def test_a_sequence_whose_dk_and_dv_do_not_fit_is_refused():
    """Past what one column block a program can hold — four times the
    cells' longest, a group of eight or of seven — the call is refused, and
    the error says what is held; a test model's call carries no compiler
    parameters."""
    for seq, h in ((65536, 32), (57344, 28)):
        with pytest.raises(ValueError, match="whole sequence"):
            fa._bwd_vmem(*_operand_shapes(seq, h, 4, 128), 128, 128, 512,
                         512)
    assert fa._bwd_vmem(*_operand_shapes(128, 4, 4, 64), 64, 64, 32,
                        32) is None


def test_the_plans_a_call_may_take():
    """(query heads a program, kv heads a kv block), most heads first: whole
    column blocks that divide the head count, eight heads at most; a
    grouped program spans whole groups or shares ONE column block of kv
    heads with the rest of them."""
    assert fa._head_plans(16, 1, 1) == [(8, 8), (4, 4), (2, 2), (1, 1)]
    assert fa._head_plans(32, 1, 2) == [(8, 8), (4, 4), (2, 2)]
    assert fa._head_plans(12, 1, 1)[0] == (6, 6)
    assert fa._head_plans(32, 8, 1) == [(8, 1), (4, 1), (2, 1), (1, 1)]
    assert fa._head_plans(16, 16, 1)[0] == (8, 1)  # two programs a kv head
    assert fa._head_plans(28, 7, 1) == [(7, 1), (1, 1)]
    assert fa._head_plans(48, 6, 1) == [(6, 1), (3, 1), (2, 1), (1, 1)]
    assert fa._head_plans(32, 4, 2) == [(8, 2), (4, 2), (2, 2)]
    assert fa._head_plans(16, 2, 1) == [(8, 4), (4, 2), (2, 1), (1, 1)]
    assert fa._head_plans(4, 1, 4) == [(4, 4)]  # the whole width (tiny)


def test_the_forward_is_the_same_bits_at_any_head_count(monkeypatch):
    """Heads are independent in the forward — no sum crosses them — so a
    program of eight heads and four programs of two write the same out and
    lse."""
    rng = np.random.default_rng(2)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 128, n * 128)), jnp.float32)
        for n in (8, 1, 1)
    )
    bias = jnp.zeros((1, 1, 128), jnp.float32)
    mask = fa._Mask(causal=True)

    def forward():
        return fa._fwd_tiled(q, k, v, bias, 128, 128, 32, 32, mask, True)

    eight = forward()
    assert fa._fwd_geometry(q, k, 128, 128, 32, 32)[4] == 8
    plans = fa._head_plans
    monkeypatch.setattr(
        fa, "_head_plans", lambda *x: [p for p in plans(*x) if p[0] <= 2]
    )
    assert fa._fwd_geometry(q, k, 128, 128, 32, 32)[4] == 2
    for got, want in zip(forward(), eight):
        np.testing.assert_array_equal(got, want)
