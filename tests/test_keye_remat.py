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
assertions: ``tests/test_remat_operands.py``, whose helpers these are; a
file of its own because that one is the suite's longest, ROADMAP C9.)"""
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
from tests import test_remat_operands as shared

shared.TINY["keye"] = ("keye_vl2_tiny", dict(
    head_dim=128, mrope_section=(16, 24, 24), num_hidden_layers=2,
    attention_block_size=16,
))


@pytest.fixture(autouse=True, scope="module")
def release_compiled_programs():
    """This file's executables go when it ends: each holds memory mappings,
    and a worker that keeps every file's crosses ``vm.max_map_count``
    (ROADMAP C9)."""
    yield
    jax.clear_caches()


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
