"""Where the decoders' shared parts live, and that moving them there moved
no parameter.

(a) By ``ast``: ``models/decoder.py`` and ``models/remat.py`` import no other
module of ``dedloc_tpu/models/``; the seven decoder files import from
``dedloc_tpu.models`` nothing but those two, and no private name of theirs.
(b) For every name of ``roles/common.MODEL_FAMILIES``: ``model_family`` agrees
with itself on a name, a config and a module, and the parameter tree of
``jax.eval_shape(model.init, ...)`` equals ``fixtures/model_param_trees.json``,
recorded from the tree BEFORE ``models/decoder.py`` existed (PR 43's parent;
a model added since — Laguna, PR 47; Keye-VL-2.0, PR 51; Kimi Linear, PR 53;
Nemotron-H, PR 57 — from the tree that added it)
by this file's own ``param_tree``:

    git archive <commit> | tar -x -C <dir>; cd <dir>
    python <this file> <out.json>

so a checkpoint written by an older tree loads into a newer one.
"""
import ast
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "dedloc_tpu", "models")
FIXTURE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "fixtures",
    "model_param_trees.json",
)
SHARED = ("decoder", "remat")
DECODERS = ("ouro", "deepseek_v3", "lfm2_moe", "smallthinker", "sdar_moe",
            "laguna", "keye_vl2", "kimi_linear", "nemotron_h")
NAMES = (
    "tiny", "large", "ouro_tiny", "ouro_2p6b", "kanana2_tiny",
    "kanana2_30b_a3b", "lfm2_tiny", "lfm2_24b_a2b", "smallthinker_tiny",
    "smallthinker_21b_a3b", "sdar_tiny", "sdar_30b_a3b", "laguna_tiny",
    "laguna_xs2_33b_a3b", "keye_vl2_tiny", "keye_vl2_30b_a3b",
    "kimi_linear_tiny", "kimi_linear_48b_a3b", "nemotron_h_tiny",
    "nemotron3_nano_30b_a3b",
)


def _model_imports(module: str):
    """(module under dedloc_tpu.models, imported name) of every import of
    ``models/<module>.py`` that reaches into ``dedloc_tpu.models``, at any
    depth (a function's own imports too)."""
    with open(os.path.join(MODELS, module + ".py")) as f:
        tree = ast.parse(f.read())
    found = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module == "dedloc_tpu.models":
                found += [(alias.name, "") for alias in node.names]
            elif node.module.startswith("dedloc_tpu.models."):
                source = node.module.split(".")[2]
                found += [(source, alias.name) for alias in node.names]
        elif isinstance(node, ast.Import):
            found += [
                (alias.name.split(".")[2], "") for alias in node.names
                if alias.name.startswith("dedloc_tpu.models.")
            ]
    return found


@pytest.mark.parametrize("module", SHARED)
def test_shared_module_imports_no_model_file(module):
    assert _model_imports(module) == []


@pytest.mark.parametrize("module", DECODERS)
def test_decoder_imports_only_the_shared_modules(module):
    imports = _model_imports(module)
    assert imports, f"models/{module}.py shares nothing?"
    for source, name in imports:
        assert source in SHARED, (
            f"models/{module}.py imports from models/{source}.py"
        )
        assert name and not name.startswith("_"), (
            f"models/{module}.py imports {name!r} from models/{source}.py"
        )


def param_tree(name: str):
    """Sorted [path, shape, dtype] of every parameter of the model
    ``build_model(name)`` builds, from shapes alone."""
    import jax
    import jax.numpy as jnp

    from dedloc_tpu.roles.common import build_model

    _cfg, model = build_model(name)
    # SDAR's stack takes a row TWICE ([noisy ; clean]); 16 is both
    ids = jnp.zeros((1, 16), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    return sorted(
        ["/".join(str(key.key) for key in path), list(leaf.shape),
         str(leaf.dtype)]
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    )


def test_every_family_name_is_a_case():
    from dedloc_tpu.roles.common import MODEL_FAMILIES

    assert sorted(NAMES) == sorted(MODEL_FAMILIES)


@pytest.mark.parametrize("name", NAMES)
def test_family_lookups_agree_and_parameters_stay(name):
    from dedloc_tpu.roles.common import (
        MODEL_FAMILIES,
        build_model,
        model_family,
    )

    cfg, model = build_model(name)
    family = model_family(name)
    assert family is MODEL_FAMILIES[name]
    assert model_family(cfg) is family
    assert model_family(model) is family
    with open(FIXTURE) as f:
        recorded = json.load(f)[name]
    assert param_tree(name) == recorded


if __name__ == "__main__":  # record the fixture from the tree this file is in
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, os.getcwd())
    with open(sys.argv[1], "w") as out:  # a leaf a line
        out.write("{\n" + ",\n".join(
            f'"{name}": [\n' + ",\n".join(
                json.dumps(leaf) for leaf in param_tree(name)
            ) + "\n]" for name in NAMES
        ) + "\n}\n")
