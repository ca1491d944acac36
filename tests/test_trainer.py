"""The SwAV collaborative role end to end (vissl trainer capability, test
pattern: config-parameterized end-to-end run asserting completion, vissl
tests/test_tasks.py:19-48). The loop it runs is ``roles/loop.py``:
tests/test_loop.py holds its contract for every model family."""
import numpy as np


def test_swav_role_end_to_end(tmp_path):
    import logging

    from dedloc_tpu.core.config import SwAVCollaborationArguments, parse_config
    from dedloc_tpu.roles.swav import run_swav
    from dedloc_tpu.utils.checkpoint import list_checkpoints

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logging.getLogger("dedloc_tpu").addHandler(_Capture())
    args = parse_config(
        SwAVCollaborationArguments,
        [
            "--dht.listen_host", "127.0.0.1",
            "--training.model_size", "tiny",
            "--training.per_device_batch_size", "2",
            "--training.gradient_accumulation_steps", "2",
            "--training.max_local_steps", "4",
            "--training.queue_length", "8",
            "--training.queue_start_step", "1",
            "--training.warmup_steps", "2",
            "--training.total_steps", "50",
            "--training.save_steps", "2",
            "--training.output_dir", str(tmp_path / "out"),
            "--training.train_log_path", str(tmp_path / "logs" / "train.jsonl"),
            # 2 boundaries of 2x2 samples per global step
            "--optimizer.target_batch_size", "8",
            "--averager.averaging_expiration", "1.0",
            "--averager.min_refresh_period", "0.1",
            "--averager.default_refresh_period", "0.3",
        ],
    )
    state = run_swav(args)
    assert int(state.step) >= 1, "should have made at least one global step"
    assert list_checkpoints(args.training.output_dir)
    # the same train-log line as the ALBERT trainer, off the same step
    # record: this boundary's values and the optimizer's running totals
    import json

    rows = [
        json.loads(line)
        for line in (tmp_path / "logs" / "train.jsonl").read_text().splitlines()
    ]
    assert rows and all(np.isfinite(r["loss"]) for r in rows)
    for row in rows:
        assert row["samples"] == 4 and row["samples_total"] % 4 == 0
        assert 0 < row["allreduce_ms"] <= row["boundary_ms"]
        assert {"data_wait", "h2d", "fwd_bwd", "drain", "opt_apply",
                "loss_sync"} <= set(row["spans_ms"])
    assert [r["global_steps_total"] for r in rows] == list(
        range(1, len(rows) + 1)
    )
    # the queue path was actually crossed (queue_start_step=1 semantics,
    # swav_1node_resnet_submit.yaml:95): not just configured, ENGAGED
    assert any("queue engaged" in m for m in records), records


def test_swav_role_resumes_from_checkpoint(tmp_path):
    """Disk resume parity with the ALBERT trainer (round 5): the newest
    checkpoint restores params+batch_stats and seeds the collaborative
    counter, so a restarted SwAV peer (or a solo continuation of a fleet
    run) picks up where the run left off instead of from scratch."""
    import logging

    from dedloc_tpu.core.config import SwAVCollaborationArguments, parse_config
    from dedloc_tpu.roles.swav import run_swav

    records = []

    class _Capture(logging.Handler):
        def emit(self, record):
            records.append(record.getMessage())

    logging.getLogger("dedloc_tpu").addHandler(_Capture())
    argv = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "tiny",
        "--training.per_device_batch_size", "2",
        "--training.gradient_accumulation_steps", "2",
        "--training.max_local_steps", "4",
        "--training.warmup_steps", "2",
        "--training.total_steps", "50",
        "--training.save_steps", "1",
        "--training.output_dir", str(tmp_path / "out"),
        "--optimizer.target_batch_size", "8",
        "--averager.averaging_expiration", "1.0",
    ]
    run_swav(parse_config(SwAVCollaborationArguments, argv))
    first_steps = [m for m in records if "applied" in m]
    assert first_steps, "first run made no global steps"
    records.clear()
    run_swav(parse_config(SwAVCollaborationArguments, argv))
    resumed = [m for m in records if "resumed from local checkpoint" in m]
    assert resumed, f"no resume log; got {records[:10]}"
    # the counter continued: the second run's first applied step is past 1
    applied = [m for m in records if "applied" in m]
    assert applied and "step 1 " not in applied[0], applied[:3]
