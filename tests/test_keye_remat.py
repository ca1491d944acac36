"""The selection under the layer remat policies (``models/remat.py``):
Keye-VL-2.0's language model through the Pallas kernels in interpreter mode —
top-8 of up to 64 keys, tiles with nothing selected among them. The
selection is an operand of the three flash kernels: KEPT with their other
operands from ``kernel_operands`` (this model's default) up, REPLAYED from
the replayed indexer below — and either way loss and every gradient leaf
are the same bits in float32, the indexer's loss through its own kernel pair
(``ops/index_loss.py``: the model under test runs the flash path) among
them; and the kept bytes at the published widths
are the shapes' arithmetic. (The other four families' cases of the same
assertions: ``tests/test_remat_operands_<family>.py``; the helpers of both
are ``tests/remat_cases.py``.) Last, what the model's accumulate_step keeps
once it is compiled for a TPU v5e (its row of ``tools/tpu_aot.py``, no chip:
``tests/tpu_aot_rows.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dedloc_tpu.models import keye_vl2
from dedloc_tpu.parallel.train_step import stash_bytes
from dedloc_tpu.roles.common import (
    build_loss_fn,
    build_model,
    drop_collator_keys,
    model_family,
)
import remat_cases as shared
from tpu_aot_rows import tpu_aot

shared.TINY["keye"] = ("keye_vl2_tiny", dict(
    head_dim=128, mrope_section=(16, 24, 24), num_hidden_layers=2,
    attention_block_size=16,
))


@pytest.fixture(autouse=True)
def blocks_of_query_rows(monkeypatch):
    """Both passes over blocks of query rows take several steps at the
    shared helpers' 64 positions."""
    monkeypatch.setattr(keye_vl2, "INDEX_BLOCK_ROWS", 32)
    monkeypatch.setattr(keye_vl2, "INDEX_LOSS_BLOCK_ROWS", 16)


@pytest.mark.parametrize(
    "policy", ["kernel_outputs", "kernel_operands", "whole_mixer"]
)
def test_the_selection_kept_or_replayed_gives_the_same_bits(policy):
    """Kept (``kernel_operands`` and up) or replayed (``kernel_outputs``,
    and ``nothing``: the top-k runs again in the backward's replay, on the
    replayed indexer's values), the backward kernels read the selection the
    forward read."""
    got_loss, got = shared._loss_and_grad("keye", policy)
    ref_loss, ref = shared._loss_and_grad("keye", "nothing")
    assert float(got_loss) == float(ref_loss)
    jax.tree_util.tree_map_with_path(  # raises on a different tree, too
        lambda path, leaf, ref_leaf: np.testing.assert_array_equal(
            leaf, ref_leaf, err_msg=jax.tree_util.keystr(path)
        ),
        got, ref,
    )


@pytest.mark.parametrize("policy,forward_sites,select_sites", [
    ("nothing", 2, 2), ("kernel_outputs", 1, 2), ("kernel_operands", 1, 1),
    ("whole_mixer", 1, 1),
])
def test_the_loss_kernels_forward_sweep_is_kept_not_replayed(policy,
                                                             forward_sites,
                                                             select_sites):
    """Behind the flash kernels the indexer's loss is a kernel pair of its
    own (``ops/index_loss.py``) — the bits above are ITS bits, kept or
    replayed. Its backward sweep reads the forward's logZ and sum pbar out
    of the forward kernel's output: a Pallas output, so every policy from
    ``kernel_outputs`` up keeps it and the replay runs no second forward
    sweep (one site a layer; two under ``nothing``), and one backward sweep
    a layer either way. The SELECTION is a kernel's output too
    (``index_select``, ``ops/index_select.py``) and is not kept for that: by
    its name, from ``kernel_operands`` up (``remat.REPLAYED_KERNELS``) — one
    site a layer there, the forward's and the replay's below."""
    cfg, _matmuls, kernels = shared._sites("keye", policy)
    layers = cfg.num_hidden_layers
    assert cfg.attention_impl == "flash"
    assert kernels["index_loss_fwd"] == forward_sites * layers
    assert kernels["index_loss_bwd"] == layers
    assert kernels["flash_sel_fwd"] == forward_sites * layers
    assert kernels["index_select"] == select_sites * layers


def test_the_selection_is_kept_with_the_operands():
    """Keye's cell (4 layers, S = 16,384): ``kernel_operands`` — this
    model's default: the room goes to the row — keeps, over
    ``kernel_outputs``, q / k / v as the kernels read them AND the int8
    [S, S] selection they read — 268 MB a layer —; ``whole_mixer`` SDAR's
    sums and norm inputs more."""
    from dedloc_tpu.models.keye_vl2 import KeyeVL2Config

    assert KeyeVL2Config().remat_policy == "kernel_operands"
    seq, cut = 16384, dict(num_hidden_layers=4, vocab_size=18992,
                           expert_shard="0/16")

    def kept(policy):
        cfg, model = build_model("keye_vl2_30b_a3b", policy, **cut)
        params = jax.eval_shape(
            lambda r: model.init(r, jnp.zeros((1, seq), jnp.int32))["params"],
            jax.random.PRNGKey(0),
        )
        batch = jax.eval_shape(lambda: drop_collator_keys(
            next(model_family(cfg).synthetic_batches(cfg, 1, seq, 0))
        ))
        return stash_bytes(
            build_loss_fn(model), params, batch, jax.random.PRNGKey(0)
        )

    outputs, operands, mixer = map(
        kept, ("kernel_outputs", "", "whole_mixer")  # "": the default
    )
    selection = 4 * seq * seq
    assert selection == 1_073_741_824
    assert operands - outputs == 4 * seq * (32 + 2 * 4) * 128 * 2 + selection
    assert mixer - operands == 4 * seq * (4096 + 512 + 2048) * 2


def test_keye_accumulate_step_reads_a_selection_and_holds_nothing_heads_by_s_by_s():
    """Keye-VL-2.0's language model at the cell's cut (four layers, 1 row of
    16,384), compiled for a v5e alone and inside its accumulate_step: the
    selected kernels — the int8 [S, S] selection a tile operand, the tile
    flags in SMEM — get through Mosaic at 32 query heads over 4 kv heads;
    under the model's default remat ``kernel_operands`` no kernel is
    replayed (4 sites each: the selection is KEPT with the operands), the
    selection is ONE ``index_select`` call a layer and the indexer's loss
    its own kernel pair over the same tiles, and nothing of either's XLA
    block loop is left; the
    tile loop's backward sums into the accumulator's twelve expert leaves;
    NOTHING of size [heads, S, S] is materialised — the largest array the
    compiled module names is 256 MB (the int8 selection itself), where ONE
    head's float32 scores are 1,024 MB and 32
    heads' bf16 ones 17 GB —; and the program's scratch stays where the cell
    was sized: under the 15.3 GB line beside 28 bytes a parameter (+ the held
    experts' bf16 copies). The tree holds 16 since PR 62 (a backup reads the
    live state); the bound stays, so that scratch does not grow into the
    room unnoticed."""
    rows = tpu_aot("sel_kernels", "index_loss_kernels", "select_kernels",
                    "keye_accumulate_step")
    # the selection's kernel alone (``ops/index_select.py``): Mosaic takes a
    # block of 256 query rows' ordered keys, [256, 16384] int32, as VMEM
    # scratch beside the resident key head and the int8 rows it writes
    assert rows.pop("select_kernels")["kernel_calls"] == {"index_select": 1}
    # the indexer's loss kernels alone (``ops/index_loss.py``): Mosaic takes
    # the forward sweep and the one backward sweep that holds the key
    # head's whole gradient, [16384, 128] float32, in VMEM
    assert rows.pop("index_loss_kernels")["kernel_calls"] == {
        "index_loss_fwd": 1, "index_loss_bwd": 1,
    }
    heads = {"heads": 32, "kv_heads": 4}
    for row in rows.values():
        assert row["flash_windows"] == {
            "flash_sel_fwd": heads, "flash_sel_bwd_tiled": heads,
        }
        # a whole group of eight a program, forward and backward (PR 58)
        assert row["flash_heads"] == {
            "flash_sel_fwd": 8, "flash_sel_bwd_tiled": 8,
        }
        assert row["flash_vmem_mb"] == {
            "flash_sel_fwd": 28.0, "flash_sel_bwd_tiled": 84.0,
        }
    assert rows["sel_kernels"]["kernel_calls"] == {
        "flash_sel_fwd": 1, "flash_sel_bwd_tiled": 1,
    }
    row = rows["keye_accumulate_step"]
    # ... and the loss's pair: one forward sweep a layer (its logZ rides in
    # a Pallas output, which the policy keeps: no replay), one backward
    assert row["kernel_calls"] == {
        "flash_sel_fwd": 4, "flash_sel_bwd_tiled": 4,
        "index_loss_fwd": 4, "index_loss_bwd": 4, "index_select": 4,
    }
    assert row["tpu_custom_calls"] == 20
    # no float32 [128, 16, 16384] index scores, no [4, 8, 128, 16384] main
    # scores, no selection cut into the loss's blocks of 128 rows — what the
    # XLA block loop made, 128 blocks a layer and direction (PR 51) — in the
    # lowered or the compiled module
    assert row["loss_block_transients"] == []
    # ... and no float32 [256, 16, 16384] index scores, no selection written
    # a block of 256 rows at a time (``s8[64,256,16384]``: the XLA loop's
    # slabs, 64 steps a layer, PR 51)
    assert row["select_block_transients"] == []
    grads = row["expert_grad_passes"]
    assert (grads["adds"], grads["zero_fills"], grads["held_casts"]) == (
        0, 0, 0
    )
    assert row["remat_policy"] == "kernel_operands"
    largest = row["largest_buffers_mb"]
    assert largest and max(mb for _shape, mb in largest) <= 256.0, largest
    assert any(shape == "s8[1,16384,16384]" for shape, _mb in largest)
    # 5,336,333,312 bytes of scratch (PR 51) beside 8.80 + 0.30 GB
    assert row["memory"]["temp_bytes"] <= 5.5e9
    held = 4 * 8 * 3 * 2048 * 768
    assert 314_396_160 * 28 + held * 2 + row["memory"]["temp_bytes"] <= 15.3e9
