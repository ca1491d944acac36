"""Is a device program the SAME program in two trees? Offline, no chip.

    python tools/program_hash.py [program ...]     # of tools/tpu_aot.PROGRAMS

One JSON line a program: the sha256 of its lowered module (StableHLO with
the Mosaic kernels inside) as ``tools/tpu_aot.py`` lowers it for a v5e. A
Mosaic kernel is serialised WITH the source locations of the Python that
built it (file paths, line numbers of ``ops/flash_attention.py``), so two
trees whose kernels are the same program differ in those bytes: the
locations are stripped (``strip-debuginfo``) before a kernel is serialised.
Run it in both trees (the parent unpacked by ``git archive``: its own
``tools/`` and ``dedloc_tpu/`` are what it imports) and compare the lines:
equal hashes are equal inputs to the same compilers. PR 40 held the eleven
programs of the eight older cells to their parent's this way, PR 47 the
thirteen of the nine (under a ``GroupedQueryAttention`` and a ``RoutedFFN``
that gained arguments) beside its own two, ``laguna_kernels`` and
``laguna_accumulate_step``, PR 48 those thirteen and ``laguna_kernels``
(under a ``GroupedQueryAttention`` whose gate became a kernel pair and a
``_pallas_outputs_saveable`` that reads a kernel's name) beside
``laguna_accumulate_step``, which it changed, and its own
``head_gate_kernels``; PR 51 all sixteen (under flash kernels, an ``attend``,
a ``GroupedQueryAttention`` and an ``apply_rope`` that gained a selection
and per-token tables: the ORDER of two multiplies in ``apply_rope`` moved
Ouro's and Laguna's text until it was put back) beside its own
``sel_kernels`` and ``keye_accumulate_step``."""
from __future__ import annotations

import hashlib
import json
import sys

import tpu_aot  # beside this file: sets the compile-only client's environment
import jax
import jax._src.tpu_custom_call as tpu_custom_call
from jax._src.lib.mlir import passmanager

from dedloc_tpu.utils.backend import lowering_for_tpu


def _without_locations(serialize):
    def stripped(module, *args, **kwargs):
        passmanager.PassManager.parse(
            "builtin.module(strip-debuginfo)", module.context
        ).run(module.operation)
        return serialize(module, *args, **kwargs)
    return stripped


def main(argv=None) -> int:
    names = list(argv if argv is not None else sys.argv[1:]) or list(
        tpu_aot.PROGRAMS
    )
    device = tpu_aot.v5e_device()
    if device is None:
        return tpu_aot.NO_V5E
    tpu_custom_call._lower_mosaic_module_to_asm = _without_locations(
        tpu_custom_call._lower_mosaic_module_to_asm
    )
    for name in names:
        with lowering_for_tpu():
            text = tpu_aot.PROGRAMS[name](device).as_text()
        print(json.dumps({
            "program": name, "jax": jax.__version__,
            "lowered_sha256": hashlib.sha256(text.encode()).hexdigest(),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
