"""The nine averaging-round metrics of ISSUE 34: their files load for
``albert_large_s512.pair`` (and for no other cell), each names a reducer
that exists, and each reads what it says it reads off a hand-built step
record — the stages as plain spans, the kinds as folded spans (``total_s``),
the wait as what is left of ``allreduce``. On a record without the spans
(the parent's program) every one of them gives nothing and raises nothing."""
import importlib
import json
import os
import types

import pytest

from benchmark import run as bench

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one stepping record of a two-peer round: allreduce 0.700 s = the five
# stages; the loop thread's kinds 0.400 s of it; 0.300 s of waiting
SPANS = [
    ["avg_wire", None, 1.0, 1.75],
    ["matchmaking", "avg_wire", 1.0, 1.035],
    ["allreduce", "avg_wire", 1.035, 1.735],
    ["ar_resolve", "allreduce", 1.035, 1.135],
    ["ar_prepare", "allreduce", 1.135, 1.155],
    ["ar_scatter", "allreduce", 1.155, 1.455],
    ["ar_straggler", "ar_gather", 1.455, 1.555],
    ["ar_gather", "allreduce", 1.455, 1.725],
    ["ar_finish", "allreduce", 1.725, 1.735],
    ["ar_partner_lag", "allreduce", 1.035, 1.075],
    ["ar_encode", "allreduce", 1.14, 1.72, 272, 0.2],
    ["ar_decode", "allreduce", 1.05, 1.72, 136, 0.1],
    ["ar_reduce", "allreduce", 1.05, 1.6, 204, 0.04],
    ["ar_copy", "allreduce", 1.14, 1.72, 137, 0.02],
    ["ar_frame", "allreduce", 1.035, 1.73, 560, 0.04],
]
EXPECTED_MS = {
    "avg.ar_resolve_ms": 100.0, "avg.ar_scatter_ms": 300.0,
    "avg.ar_gather_ms": 270.0, "avg.ar_partner_lag_ms": 40.0,
    "avg.ar_encode_ms": 200.0, "avg.ar_decode_ms": 100.0,
    "avg.ar_reduce_ms": 40.0, "avg.ar_frame_ms": 40.0,
    "avg.ar_wait_ms": 300.0,
}


def _cell(name):
    with open(os.path.join(HERE, "workloads", f"{name}.json")) as f:
        return json.load(f)


def _run(spans):
    records = [
        {"stepped": True, "spans": spans, "phases": {"avg_wire": 0.6}},
        {"stepped": False, "spans": [["fwd_bwd", None, 0.0, 0.2]],
         "phases": {"fwd_bwd": 0.2}},
    ]
    return types.SimpleNamespace(step_records=records)


def test_the_nine_metric_files_load_for_the_pair_cell_only():
    names = {
        m["name"] for m in bench.load_metrics(
            _cell("albert_large_s512.pair"), "per_layer"
        )
    }
    assert set(EXPECTED_MS) <= names
    for other in ("albert_large_s512.solo", "ouro_2p6b_s4096.solo"):
        assert not set(EXPECTED_MS) & {
            m["name"] for m in bench.load_metrics(_cell(other), "per_layer")
        }


@pytest.mark.parametrize("name", sorted(EXPECTED_MS))
def test_each_metric_reads_its_span(name):
    with open(os.path.join(HERE, "metrics", f"{name}.json")) as f:
        metric = json.load(f)
    assert metric["layer"] == "averaging" and metric["unit"] == "ms"
    assert metric["source"] == "program_span" and metric["better"] == "lower"
    assert metric["moves"] == "samples_per_s_per_chip"
    reducer = importlib.import_module(
        f"benchmark.reducers.{metric['reducer']}"
    )
    assert reducer.reduce(_run(SPANS), metric["params"]) == pytest.approx(
        EXPECTED_MS[name]
    )
    # the parent's program records none of the ar_* spans: nothing to read
    parent = [s for s in SPANS if not s[0].startswith("ar_")]
    assert reducer.reduce(_run(parent), metric["params"]) is None
    assert reducer.reduce(
        types.SimpleNamespace(step_records=[]), metric["params"]
    ) is None


def test_the_equalities_the_metrics_are_built_for():
    """Stages tile ``allreduce``; kinds + wait = ``allreduce`` (``ar_copy``
    has no metric of its own and is inside the residual's subtrahend)."""
    by_name = {s[0]: s for s in SPANS}
    allreduce = by_name["allreduce"][3] - by_name["allreduce"][2]
    stages = sum(
        by_name[n][3] - by_name[n][2] for n in
        ("ar_resolve", "ar_prepare", "ar_scatter", "ar_gather", "ar_finish")
    )
    assert stages == pytest.approx(allreduce)
    kinds = sum(EXPECTED_MS[f"avg.ar_{k}_ms"] for k in
                ("encode", "decode", "reduce", "frame")) + 20.0  # ar_copy
    assert kinds + EXPECTED_MS["avg.ar_wait_ms"] == pytest.approx(
        allreduce * 1e3
    )
