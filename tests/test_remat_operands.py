"""The layer remat policies ``kernel_operands`` and ``whole_mixer``
(``models/remat.py``). Under the first a decoder layer keeps what its Pallas
backward kernels READ — q / k / v as the flash kernels take them, the short
convolution's B | C | u — beside what the forward ones wrote; under the
second, the default of the three families with memory to spend, also the
stream after the mixer and the input of a per-head q / k RMSNorm, so the
replay of a layer runs no matmul of the mixer. For each of the two and each
of the four families (Laguna's with a gate a head on the kernels' output): the same bits as ``kernel_outputs`` and ``nothing``
(loss and every gradient leaf); the engagement count with no chip (a
projection whose output is kept runs once a layer in the gradient, not twice;
every kernel still once); and the mechanism's counter ``remat.kept_bytes``
against the shapes' arithmetic at the published widths."""
import pytest

from dedloc_tpu.models.deepseek_v3 import DeepseekV3Config
from dedloc_tpu.models.laguna import LagunaConfig
from dedloc_tpu.models.lfm2_moe import Lfm2MoeConfig
from dedloc_tpu.models.ouro import OuroConfig
from dedloc_tpu.models.remat import remat_policy_object
from dedloc_tpu.models.sdar_moe import SdarMoeConfig
from dedloc_tpu.models.smallthinker import SmallThinkerConfig
from dedloc_tpu.roles.common import build_model
from remat_cases import POLICIES


def test_the_table_and_the_six_defaults():
    """One table: the new row resolves, a name that is none of its rows
    still raises, and each decoder family states the default its cell's
    memory allows."""
    for row in POLICIES + ("kernel_outputs",):
        assert callable(remat_policy_object(row))
    with pytest.raises(ValueError, match="unknown remat_policy"):
        remat_policy_object("kernel_operand")
    assert {
        cls.__name__: cls().remat_policy for cls in (
            SmallThinkerConfig, SdarMoeConfig, Lfm2MoeConfig,
            DeepseekV3Config, OuroConfig, LagunaConfig,
        )
    } == {
        "LagunaConfig": "whole_mixer",
        "SmallThinkerConfig": "whole_mixer",
        "SdarMoeConfig": "whole_mixer",
        "Lfm2MoeConfig": "whole_mixer",
        "DeepseekV3Config": "kernel_outputs",
        "OuroConfig": "kernel_outputs",
    }
    # the override the trainer's flag passes reaches the model
    cfg, _model = build_model("smallthinker_tiny", "kernel_outputs")
    assert cfg.remat_policy == "kernel_outputs"
