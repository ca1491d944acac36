"""Role entry points driven in-process on the virtual CPU mesh: trainer
(single-peer synthetic run with checkpointing), coordinator (metrics
aggregation loop), dht bootstrap node, and two collaborating trainer peers."""
import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from dedloc_tpu.collaborative.metrics import LocalMetrics, publish_metrics
from dedloc_tpu.core.config import CollaborationArguments, parse_config
from dedloc_tpu.roles.aux import run_aux
from dedloc_tpu.roles.coordinator import (
    CoordinatorExtraArguments,
    run_coordinator,
)
from dedloc_tpu.roles.dht_node import run_dht_node
from dedloc_tpu.roles.trainer import run_trainer
from dedloc_tpu.utils.checkpoint import list_checkpoints


def _args(tmp_path, argv=()):
    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "tiny",
        "--training.seq_length", "64",
        "--training.per_device_batch_size", "2",
        "--training.gradient_accumulation_steps", "2",
        "--training.warmup_steps", "2",
        "--training.total_steps", "50",
        "--training.output_dir", str(tmp_path / "out"),
        "--averager.averaging_expiration", "1.0",
        "--averager.min_refresh_period", "0.1",
        "--averager.default_refresh_period", "0.3",
    ]
    return parse_config(CollaborationArguments, base + list(argv))


def test_dht_node_runs(tmp_path):
    run_dht_node(_args(tmp_path), keepalive_period=0.01, max_iterations=2)


def test_trainer_single_peer_makes_global_steps(tmp_path):
    # target batch 8 = 2 boundaries of 2x2 samples => global step every 2
    train_log = tmp_path / "logs" / "not_yet_there" / "train.jsonl"
    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "8",
            "--training.max_local_steps", "7",
            "--training.save_steps", "1",
            "--training.train_log_path", str(train_log),
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 2
    ckpts = list_checkpoints(args.training.output_dir)
    assert ckpts, "trainer should have saved checkpoints"
    # the log's directory is the trainer's to create
    rows = [json.loads(line) for line in train_log.read_text().splitlines()]
    assert len(rows) >= 2 and all(np.isfinite(r["loss"]) for r in rows)
    # each line holds THIS global step's values, read off its step record
    # (no recent means), and the optimizer's running totals
    for row in rows:
        assert row["samples"] == 4
        assert 0 < row["data_wait_ms"] < row["boundary_ms"]
        assert 0 < row["allreduce_ms"] < row["boundary_ms"]
        assert {"data_wait", "fwd_bwd", "drain", "grad_flatten", "opt_apply",
                "collab", "loss_sync"} <= set(row["spans_ms"])
        assert row["spans_ms"]["data_wait"] == pytest.approx(
            row["data_wait_ms"], abs=1e-3
        )
    totals = [r["samples_total"] for r in rows]
    assert totals == sorted(totals) and totals[-1] <= 7 * 4
    assert [r["global_steps_total"] for r in rows] == list(
        range(1, len(rows) + 1)
    )
    # telemetry is off: no registry, no event log, no profile — the output
    # directory holds the checkpoints and the train log's own directory
    from dedloc_tpu.telemetry import registry

    assert registry.active() is None
    assert sorted(p.name for p in tmp_path.iterdir()) == ["logs", "out"]


def test_trainer_resumes_from_checkpoint(tmp_path):
    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "8",
            "--training.max_local_steps", "5",
            "--training.save_steps", "1",
        ],
    )
    state = run_trainer(args)
    first_run_step = int(state.step)
    assert first_run_step >= 1
    # second run resumes from disk: global step monotonically continues —
    # including the COLLABORATIVE counter (fresh DHT, nobody to pull state
    # from: round ids/metrics must continue from the checkpoint, not step 0)
    import logging

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    capture = _Capture()
    logging.getLogger("dedloc_tpu").addHandler(capture)
    try:
        state2 = run_trainer(args)
    finally:
        logging.getLogger("dedloc_tpu").removeHandler(capture)
    assert int(state2.step) >= first_run_step
    steps_logged = [
        int(m.split("global step ")[1].split(":")[0])
        for m in records if m.startswith("global step ") and ":" in m
    ]
    assert steps_logged and min(steps_logged) > first_run_step, (
        f"collaborative counter restarted: {steps_logged[:3]} after "
        f"first run ended at {first_run_step}"
    )


def test_coordinator_aggregates_published_metrics(tmp_path):
    from dedloc_tpu.roles.common import build_dht

    args = _args(tmp_path)
    log_path = str(tmp_path / "metrics.jsonl")
    peer_dht, public_key = build_dht(args)
    try:
        publish_metrics(
            peer_dht,
            args.dht.experiment_prefix,
            public_key,
            LocalMetrics(
                step=1,
                samples_per_second=12.5,
                samples_accumulated=64,
                loss=6.0,
                mini_steps=3,
            ),
        )
        time.sleep(0.2)
        coord_args = _args(
            tmp_path,
            ["--dht.initial_peers", peer_dht.get_visible_address()],
        )
        run_coordinator(
            coord_args,
            CoordinatorExtraArguments(
                refresh_period=0.1, metrics_log_path=log_path
            ),
            max_iterations=5,
        )
    finally:
        peer_dht.shutdown()
    with open(log_path) as f:
        lines = [json.loads(line) for line in f]
    assert lines and lines[-1]["step"] == 1
    assert lines[-1]["alive_peers"] == 1
    assert abs(lines[-1]["loss"] - 2.0) < 1e-6  # 6.0 / 3 mini-steps


def test_two_trainer_roles_collaborate(tmp_path):
    """Two trainer-role peers bootstrap off one DHT node, form a real
    2-peer averaging group, and both advance the global step — the full
    role stack end-to-end."""
    import logging

    from dedloc_tpu.roles.common import build_dht

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    capture = _Capture()
    logging.getLogger("dedloc_tpu").addHandler(capture)
    root_args = _args(tmp_path)
    root_dht, _ = build_dht(root_args)
    try:
        addr = root_dht.get_visible_address()
        results, errors = {}, []

        def peer(idx):
            try:
                args = _args(
                    tmp_path,
                    [
                        "--dht.initial_peers", addr,
                        # target sized so a round takes SECONDS (~13 solo
                        # boundaries): sub-second rounds sit below the DHT
                        # record-propagation latency, where a fast peer's
                        # solo cadence can outrun the partner's visibility
                        # no matter how long both run — the protocol
                        # targets the coordinated regime (real rounds are
                        # 5s+), so the test must too
                        "--optimizer.target_batch_size", "256",
                        # budget must keep BOTH peers stepping through
                        # cold-start skew AND round-assembly waits:
                        # boundaries are ~0.25s and keep being consumed
                        # while the global target fills, so a small budget
                        # expires mid-collaboration (a peer once exited
                        # 0.6s after the first joint round, stranding its
                        # partner into two failed windows)
                        "--training.max_local_steps", "600",
                        "--training.save_steps", "0",
                        "--training.output_dir", str(tmp_path / f"peer{idx}"),
                        "--training.seed", str(idx),
                        # generous straggler window: early assembly makes the
                        # aligned path instant; this bound only pays when the
                        # partner is late under parallel-suite CPU load
                        "--averager.averaging_expiration", "15",
                    ],
                )
                results[idx] = run_trainer(args)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=peer, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors
        assert len(results) == 2
        assert max(int(s.step) for s in results.values()) >= 1
        # a REAL group formed (failed-round local applies also advance
        # steps and would otherwise mask a dead averaging path)
        assert any("group=2" in m for m in records), "no 2-peer group formed"
    finally:
        logging.getLogger("dedloc_tpu").removeHandler(capture)
        root_dht.shutdown()


def test_two_slice_peers_hybrid_ici_dcn(tmp_path):
    """The TPU-native two-level scheme end-to-end (SURVEY.md §1 swav seam,
    §2.6 mapping): each peer is a SLICE — a 4-device data-parallel mesh
    carved from the virtual 8-CPU pool — whose micro-batch grad mean rides
    XLA collectives (the ICI path), while gradients average BETWEEN slices
    through the DHT/TCP averager (the DCN path)."""
    from dedloc_tpu.roles.common import build_dht

    root_args = _args(tmp_path)
    root_dht, _ = build_dht(root_args)
    try:
        addr = root_dht.get_visible_address()
        results, errors = {}, []

        def slice_peer(idx):
            try:
                args = _args(
                    tmp_path,
                    [
                        "--dht.initial_peers", addr,
                        "--optimizer.target_batch_size", "32",
                        "--training.max_local_steps", "10",
                        "--training.save_steps", "0",
                        "--training.mesh_devices", "4",
                        "--training.mesh_device_offset", str(idx * 4),
                        "--averager.averaging_expiration", "15",
                        "--training.output_dir",
                        str(tmp_path / f"slice{idx}"),
                        "--training.seed", str(idx),
                    ],
                )
                results[idx] = run_trainer(args)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=slice_peer, args=(i,)) for i in (0, 1)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 2
        # each boundary contributes 2 (per-dev) x 4 (mesh) x 2 (accum) = 16
        # samples; two slices reach target 32 together => steps advance
        assert max(int(s.step) for s in results.values()) >= 1
    finally:
        root_dht.shutdown()


def test_trainer_zero_sharding_on_mesh(tmp_path):
    """ZeRO-1 wired end-to-end through the trainer role (VERDICT r1 item 5):
    a slice peer with --training.zero_sharding shards its LAMB moments over
    the mesh's data axis and still makes global steps — on the flagship
    recipe, whose Pallas kernels run under shard_map on a mesh (a TPU
    cannot partition them any other way)."""
    from jax.sharding import PartitionSpec as P

    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "16",
            "--training.max_local_steps", "5",
            "--training.save_steps", "0",
            "--training.mesh_devices", "4",
            "--training.zero_sharding", "true",
            "--training.attention_impl", "flash",
            "--training.remat_policy", "fused_ln",
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 1
    # nothing sits on one device alone
    assert {
        len(leaf.sharding.device_set)
        for leaf in jax.tree.leaves((state.params, state.opt_state))
    } == {4}
    # the moments really are sharded: some leaf of the opt state must carry
    # a non-replicated PartitionSpec over the data axis
    specs = [
        getattr(leaf.sharding, "spec", P())
        for leaf in jax.tree.leaves(state.opt_state)
        if hasattr(leaf, "sharding")
    ]
    assert any(
        "data" in str(spec) for spec in specs
    ), f"no opt-state leaf sharded over the data axis: {specs}"


def test_trainer_ring_attention_sequence_parallel(tmp_path):
    """attention_impl='ring' under a dp x sp slice mesh (VERDICT r1 item 9):
    tiny-ALBERT trains with the sequence sharded over 2 devices and still
    makes global steps with finite falling loss."""
    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "16",
            "--training.max_local_steps", "5",
            "--training.save_steps", "0",
            "--training.mesh_devices", "4",
            "--training.mesh_seq_devices", "2",
            "--training.attention_impl", "ring",
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 1


def test_streaming_trainer_on_real_text(tmp_path):
    """VERDICT r1 weak item 6: the sahajbert streaming path end-to-end on
    REAL text — harvested English prose mixed with genuine Bengali sentences
    (danda-split, non-ASCII) through tokenizer training, the weighted lazy
    mix, the per-peer shuffle buffer, and on-the-fly tokenize+mask."""
    import dedloc_tpu
    from dedloc_tpu.data.corpus import harvest
    from dedloc_tpu.data.tokenizer import FastTokenizer, train_unigram_tokenizer

    docs = list(
        harvest(
            roots=[os.path.dirname(dedloc_tpu.__file__)],
            min_words=30, max_docs=120,
        )
    )
    assert len(docs) >= 20
    bengali = [
        "বাংলা ভাষা দক্ষিণ এশিয়ার একটি প্রধান ভাষা। এটি বাংলাদেশের রাষ্ট্রভাষা এবং "
        "ভারতের পশ্চিমবঙ্গ রাজ্যের সরকারি ভাষা। পৃথিবীতে প্রায় ত্রিশ কোটি মানুষ বাংলায় "
        "কথা বলে। বাংলা সাহিত্যের ইতিহাস হাজার বছরের পুরনো।",
        "রবীন্দ্রনাথ ঠাকুর বাংলা সাহিত্যের সবচেয়ে পরিচিত কবি। তিনি গীতাঞ্জলির জন্য "
        "নোবেল পুরস্কার পেয়েছিলেন। তাঁর গান দুই দেশের জাতীয় সংগীত হয়েছে। তাঁর "
        "লেখা আজও মানুষ ভালোবাসে।",
    ] * 10
    en_path = tmp_path / "en.txt"
    bn_path = tmp_path / "bn.txt"
    en_path.write_text("\n".join(docs), encoding="utf-8")
    bn_path.write_text("\n".join(bengali), encoding="utf-8")

    tok = train_unigram_tokenizer(docs + bengali, vocab_size=512)
    tok_path = tmp_path / "tokenizer.json"
    FastTokenizer(tok).save(str(tok_path))

    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "8",
            "--training.max_local_steps", "7",
            "--training.save_steps", "0",
            "--training.streaming_files", str(en_path), str(bn_path),
            "--training.streaming_weights", "0.77", "0.23",
            "--training.streaming_buffer_size", "64",
            "--training.tokenizer_path", str(tok_path),
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 2


def test_evaluate_role_reports_holdout_loss(tmp_path):
    """The evaluate role: train briefly on tokenized shards, then measure
    held-out MLM loss from the saved checkpoint (deterministic per seed)."""
    import numpy as np

    from dedloc_tpu.data.disk import write_shards
    from dedloc_tpu.data.mlm import SpecialTokens
    from dedloc_tpu.roles.evaluate import EvalArguments, run_eval

    # tiny synthetic tokenized dataset on disk (the disk-reader layout)
    rng = np.random.default_rng(0)
    n, seq = 64, 64
    ids = rng.integers(5, 512, (n, seq)).astype(np.int32)
    batches = iter(
        [
            {
                "input_ids": ids,
                "token_type_ids": np.zeros((n, seq), np.int32),
                "special_tokens_mask": np.zeros((n, seq), np.int32),
                "sop_labels": rng.integers(0, 2, (n,)).astype(np.int32),
            }
        ]
    )
    data_dir = tmp_path / "tok"
    write_shards(str(data_dir), batches)

    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "8",
            "--training.max_local_steps", "5",
            "--training.save_steps", "1",
            "--training.dataset_path", str(data_dir),
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 1

    result = run_eval(args, EvalArguments(max_batches=4))
    assert result["checkpoint_step"] >= 1
    assert np.isfinite(result["mlm_loss"]) and result["mlm_loss"] > 0
    again = run_eval(args, EvalArguments(max_batches=4))
    assert again["mlm_loss"] == result["mlm_loss"]  # deterministic


@pytest.mark.slow  # ~109s of real trainer rounds — the #1 tier-1
# wall-clock offender (tools/t1_budget.py). The transport-level contract
# (client-mode peer collaborates through a circuit relay, real group of 2)
# now runs tier-1 in seconds on the simulated transport:
# tests/test_simulator.py::test_sim_port_client_mode_peers_collaborate_via_relay
def test_client_mode_trainer_collaborates_via_relay(tmp_path):
    """A firewalled trainer (--dht.client_mode + --dht.relay) leads/joins
    rounds through a public peer's circuit relay — the full role stack with
    no inbound connectivity on one side. Asserts a REAL group of 2 formed
    (failed-round local-apply would otherwise keep steps advancing and mask
    a dead relay)."""
    import logging

    # the package logger sets propagate=False, so capture with our own
    # handler instead of caplog
    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    capture = _Capture()
    logging.getLogger("dedloc_tpu").addHandler(capture)
    from dedloc_tpu.averaging.averager import DecentralizedAverager
    from dedloc_tpu.roles.common import build_dht

    root_args = _args(tmp_path)
    root_dht, _ = build_dht(root_args)
    # transport-only relay host (separate prefix: it never joins the
    # experiment's rounds; any public peer would serve equally)
    relay_host = DecentralizedAverager(
        root_dht, "relayhost", listen_host="127.0.0.1"
    )
    try:
        addr = root_dht.get_visible_address()
        relay_addr = f"127.0.0.1:{relay_host.server.port}"
        results, errors = {}, []

        def peer(idx, extra):
            try:
                args = _args(
                    tmp_path,
                    [
                        "--dht.initial_peers", addr,
                        # seconds-scale rounds + a budget that outlasts
                        # compile skew and round-assembly waits, for the
                        # same reasons as in
                        # test_two_trainer_roles_collaborate above
                        "--optimizer.target_batch_size", "256",
                        "--training.max_local_steps", "600",
                        "--training.save_steps", "0",
                        "--training.output_dir", str(tmp_path / f"rp{idx}"),
                        "--training.seed", str(idx),
                        "--averager.averaging_expiration", "15",
                    ] + extra,
                )
                results[idx] = run_trainer(args)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=peer, args=(0, []), daemon=True),
            threading.Thread(
                target=peer,
                args=(1, ["--dht.client_mode", "true",
                          "--dht.relay", relay_addr]),
                daemon=True,
            ),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not errors, errors
        assert len(results) == 2
        assert max(int(s.step) for s in results.values()) >= 1
        # the relay actually carried a round: some global step applied with
        # a group of 2 (solo fallbacks log group=1)
        assert any(
            "group=2" in msg for msg in records
        ), "no 2-peer group ever formed through the relay"
    finally:
        logging.getLogger("dedloc_tpu").removeHandler(capture)
        relay_host.shutdown()
        root_dht.shutdown()


def test_join_command_flag_mapping():
    from dedloc_tpu.join import build_trainer_argv

    argv = build_trainer_argv([
        "--initial_peers", "10.0.0.1:31337",
        "--experiment_prefix", "myrun",
        "--username", "alice", "--credential", "pw",
        "--client_mode", "--relay", "10.0.0.2:4000",
        "--training.max_local_steps", "3",
    ])
    assert argv[:4] == ["--dht.initial_peers", "10.0.0.1:31337",
                        "--dht.experiment_prefix", "myrun"]
    assert "--auth.username" in argv and "--dht.client_mode" in argv
    assert argv[-2:] == ["--training.max_local_steps", "3"]


def test_join_command_verbatim_gated(tmp_path):
    """VERDICT r2 item 7 done-criterion: the DOCUMENTED one-command join
    path (python -m dedloc_tpu.join --initial_peers ... --username ...)
    authorizes against the coordinator's AuthService, joins the DHT, and
    trains — driven verbatim as a subprocess. A wrong credential fails
    fast with a clear error."""
    import subprocess
    import sys

    from dedloc_tpu.core.auth import AllowlistAuthServer, AuthService
    from dedloc_tpu.roles.common import build_dht

    root_args = _args(tmp_path)
    root_dht, _ = build_dht(root_args)
    auth_server = AllowlistAuthServer({"volunteer": "s3cret"})

    async def _attach(node):
        AuthService(node.server, auth_server)

    root_dht.run_coroutine(_attach)
    try:
        addr = root_dht.get_visible_address()
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        cmd = [
            sys.executable, "-m", "dedloc_tpu.join",
            "--initial_peers", addr,
            "--experiment_prefix", root_args.dht.experiment_prefix,
            "--username", "volunteer", "--credential", "s3cret",
            "--batch_size", "2",
            # tiny-run passthrough so the smoke finishes in seconds
            "--training.model_size", "tiny",
            "--training.seq_length", "64",
            "--training.gradient_accumulation_steps", "2",
            "--training.max_local_steps", "5",
            "--training.save_steps", "0",
            "--optimizer.target_batch_size", "8",
            "--training.output_dir", str(tmp_path / "vol"),
        ]
        out = subprocess.run(
            cmd, env=env, capture_output=True, text=True, timeout=240,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert out.returncode == 0, out.stderr[-3000:]
        assert "left the collaboration at global step" in out.stdout

        bad = subprocess.run(
            cmd[:8] + ["wrong"] + cmd[9:], env=env, capture_output=True,
            text=True, timeout=120,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert bad.returncode != 0
        assert "not authorized" in (bad.stderr + bad.stdout)
    finally:
        root_dht.shutdown()


def test_trainer_tensor_parallel_on_mesh(tmp_path):
    """VERDICT r3 #7: tensor parallelism reachable from the trainer CLI —
    a dp2 x tp2 slice peer shards params by the Megatron-style rules, still
    makes global steps, and composes with ZeRO for the rest of the moments."""
    from jax.sharding import PartitionSpec as P

    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "16",
            "--training.max_local_steps", "5",
            "--training.save_steps", "0",
            "--training.mesh_devices", "4",
            "--training.mesh_model_devices", "2",
            "--training.zero_sharding", "true",
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 1
    import jax

    param_specs = [
        str(getattr(leaf.sharding, "spec", P()))
        for leaf in jax.tree.leaves(state.params)
        if hasattr(leaf, "sharding")
    ]
    assert any("model" in s for s in param_specs), (
        f"no param leaf sharded over the model axis: {param_specs}"
    )
    opt_specs = [
        str(getattr(leaf.sharding, "spec", P()))
        for leaf in jax.tree.leaves(state.opt_state)
        if hasattr(leaf, "sharding")
    ]
    assert any("model" in s for s in opt_specs), "TP moments must follow params"
    assert any("data" in s for s in opt_specs), (
        "ZeRO must shard what TP left replicated"
    )


def test_trainer_pipeline_parallel_on_mesh(tmp_path):
    """VERDICT r4 #3: pipeline parallelism reachable from the trainer CLI —
    a dp2 x pp2 slice peer stages the shared block across the pipe axis
    (GPipe under shard_map, parallel/pipeline.py) and still makes global
    steps with a finite loss. The param tree matches the scanned model, so
    the collaborative grad schema is unchanged."""
    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "16",
            "--training.max_local_steps", "4",
            "--training.save_steps", "0",
            "--training.mesh_devices", "4",
            "--training.mesh_pipe_devices", "2",
            "--training.pipe_microbatches", "4",
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 1
    import jax

    # same leaf paths as the non-pipelined model: encoder/layer/block/...
    paths = [
        jax.tree_util.keystr(p)
        for p, _ in jax.tree_util.tree_leaves_with_path(state.params)
    ]
    assert any("['encoder']['layer']['block']" in p for p in paths), paths


def test_trainer_pipe_rejects_tp_and_seq(tmp_path):
    args = _args(
        tmp_path,
        [
            "--training.mesh_devices", "8",
            "--training.mesh_pipe_devices", "2",
            "--training.mesh_model_devices", "2",
        ],
    )
    with pytest.raises(ValueError, match="data axis only"):
        run_trainer(args)


def test_trainer_moe_expert_parallel_on_mesh(tmp_path):
    """VERDICT r4 #3: the Switch-MoE ALBERT variant reachable from the
    trainer CLI — dp2 x ep2, experts sharded over the expert axis (the
    dispatch einsums lower to all-to-alls), aux loss flowing into training,
    global steps with finite loss."""
    from jax.sharding import PartitionSpec as P

    args = _args(
        tmp_path,
        [
            "--optimizer.target_batch_size", "16",
            "--training.max_local_steps", "4",
            "--training.save_steps", "0",
            "--training.mesh_devices", "4",
            "--training.mesh_expert_devices", "2",
            "--training.moe_experts", "4",
            "--training.zero_sharding", "true",
        ],
    )
    state = run_trainer(args)
    assert int(state.step) >= 1
    import jax

    by_path = {
        jax.tree_util.keystr(p): leaf
        for p, leaf in jax.tree_util.tree_leaves_with_path(state.params)
    }
    moe_leaves = {k: v for k, v in by_path.items() if "moe_w" in k}
    assert moe_leaves, f"no MoE leaves in {sorted(by_path)[:5]}..."
    specs = [
        str(getattr(leaf.sharding, "spec", P())) for leaf in moe_leaves.values()
    ]
    assert any("expert" in s for s in specs), (
        f"experts not sharded over the expert axis: {specs}"
    )
    # moments follow the expert layout; ZeRO shards the rest over data
    opt_specs = [
        str(getattr(leaf.sharding, "spec", P()))
        for leaf in jax.tree.leaves(state.opt_state)
        if hasattr(leaf, "sharding")
    ]
    assert any("expert" in s for s in opt_specs)
    assert any("data" in s for s in opt_specs)
