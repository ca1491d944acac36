"""Checkpoint hub publication (run_first_peer.py:123-147 capability): git
uploader against a local bare remote, directory mirror, coordinator wiring."""
import os
import subprocess

import numpy as np

from dedloc_tpu.utils.checkpoint import save_checkpoint
from dedloc_tpu.utils.hub import (
    build_upload_fn,
    directory_mirror_uploader,
    git_hub_uploader,
)


def _ckpt(tmp_path, step, value):
    return save_checkpoint(
        str(tmp_path / "ckpts"), step,
        {"w": np.full((4,), value, np.float32)},
        metadata={"step": step}, save_total_limit=None,
    )


def test_git_uploader_pushes_to_bare_remote(tmp_path):
    remote = str(tmp_path / "hub.git")
    subprocess.run(
        ["git", "init", "--bare", "--initial-branch", "main", remote],
        check=True, capture_output=True,
    )
    upload = git_hub_uploader(str(tmp_path / "work"), remote)

    upload(_ckpt(tmp_path, 5, 1.0), 5)
    upload(_ckpt(tmp_path, 10, 2.0), 10)
    # identical re-publish is a no-op commit-wise
    upload(_ckpt(tmp_path, 10, 2.0), 10)

    log = subprocess.run(
        ["git", "-C", remote, "log", "--format=%s", "main"],
        check=True, capture_output=True, text=True,
    ).stdout.strip().splitlines()
    assert log == [
        "checkpoint at collaboration step 10",
        "checkpoint at collaboration step 5",
    ]
    files = subprocess.run(
        ["git", "-C", remote, "ls-tree", "--name-only", "main"],
        check=True, capture_output=True, text=True,
    ).stdout.split()
    assert "state.bin" in files and "step.txt" in files


def test_git_uploader_without_remote_commits_locally(tmp_path):
    work = str(tmp_path / "work")
    upload = git_hub_uploader(work)
    upload(_ckpt(tmp_path, 1, 3.0), 1)
    log = subprocess.run(
        ["git", "-C", work, "log", "--format=%s"],
        check=True, capture_output=True, text=True,
    ).stdout.strip()
    assert "step 1" in log


def test_directory_mirror_uploader(tmp_path):
    dest = str(tmp_path / "mirror")
    upload = directory_mirror_uploader(dest)
    upload(_ckpt(tmp_path, 7, 1.5), 7)
    assert os.path.exists(os.path.join(dest, "checkpoint-7", "state.bin"))
    assert open(os.path.join(dest, "latest")).read() == "7"


def test_build_upload_fn_resolution(tmp_path):
    assert build_upload_fn() is None
    assert build_upload_fn(hub_mirror_dir=str(tmp_path / "m")) is not None
    assert build_upload_fn(hub_git_dir=str(tmp_path / "g")) is not None


# ---------------------------------------------- coordinator upload contract
# (_pull_and_save's seam: one upload in flight at a time, a skipped step is
# covered by the next interval, a hub blip never kills the coordinator, and
# the sharded manifest rides the published checkpoint dir)


class _FakeAverager:
    """Stands in for the coordinator's client-mode averager."""

    def __init__(self, tree=None, step=1):
        self.tree = tree
        self.step = step

    def load_state_from_peers(self, *a, **k):
        if self.tree is None:
            return None
        return {"step": self.step, "local_step": self.step}, self.tree


def _coordinator_args(tmp_path, shard_size=0):
    from dedloc_tpu.core.config import CollaborationArguments, parse_config

    return parse_config(
        CollaborationArguments,
        ["--training.output_dir", str(tmp_path / "out"),
         "--training.save_total_limit", "3",
         "--checkpoint.shard_size", str(shard_size)],
    )


def test_pull_and_save_one_upload_in_flight(rng, tmp_path):
    import threading

    from dedloc_tpu.roles.coordinator import _pull_and_save

    args = _coordinator_args(tmp_path)
    gate = threading.Event()
    uploaded = []

    def slow_upload(path, step):
        uploaded.append((step, path))
        assert gate.wait(timeout=30), "test never released the upload gate"

    tree = {"w": rng.standard_normal((4,)).astype(np.float32)}
    uploads = {"thread": None}
    _pull_and_save(args, _FakeAverager(tree, 1), 1, slow_upload, uploads)
    first = uploads["thread"]
    assert first is not None and first.is_alive()
    # a new checkpoint while the push is in flight: saved, upload SKIPPED
    _pull_and_save(args, _FakeAverager(tree, 2), 2, slow_upload, uploads)
    assert uploads["thread"] is first, "second upload must not launch"
    assert [s for s, _ in uploaded] == [1]
    assert os.path.isdir(os.path.join(str(tmp_path / "out"), "checkpoint-2"))
    gate.set()
    first.join(timeout=10)
    # the next interval covers the skipped step: latest state goes up
    _pull_and_save(args, _FakeAverager(tree, 3), 3, slow_upload, uploads)
    uploads["thread"].join(timeout=10)
    assert [s for s, _ in uploaded] == [1, 3]
    assert uploaded[-1][1].endswith("checkpoint-3")


def test_pull_and_save_upload_failure_contained(rng, tmp_path):
    """A hub blip fails ONE push, not the coordinator: the exception stays
    on the upload thread and the next interval uploads again."""
    from dedloc_tpu.roles.coordinator import _pull_and_save

    args = _coordinator_args(tmp_path)
    calls = []

    def flaky_upload(path, step):
        calls.append(step)
        if step == 1:
            raise RuntimeError("remote hung up")

    tree = {"w": np.ones((4,), np.float32)}
    uploads = {"thread": None}
    _pull_and_save(args, _FakeAverager(tree, 1), 1, flaky_upload, uploads)
    uploads["thread"].join(timeout=10)
    _pull_and_save(args, _FakeAverager(tree, 2), 2, flaky_upload, uploads)
    uploads["thread"].join(timeout=10)
    assert calls == [1, 2]


def test_pull_and_save_no_providers_skips_everything(tmp_path):
    from dedloc_tpu.roles.coordinator import _pull_and_save

    args = _coordinator_args(tmp_path)
    uploads = {"thread": None}
    _pull_and_save(args, _FakeAverager(None), 5, None, uploads)
    assert uploads["thread"] is None
    assert not os.path.isdir(os.path.join(str(tmp_path / "out"),
                                          "checkpoint-5"))


def test_pull_and_save_publishes_sharded_manifest(rng, tmp_path):
    """With --checkpoint.shard_size set, every pulled state also lands as a
    durable manifest + content-addressed shards, and the manifest rides the
    published checkpoint dir so hub consumers can verify shard integrity."""
    from dedloc_tpu.checkpointing import CheckpointManifest, ShardStore
    from dedloc_tpu.roles.coordinator import _pull_and_save

    args = _coordinator_args(tmp_path, shard_size=4)
    uploaded = []
    tree = {"w": rng.standard_normal((11,)).astype(np.float32)}
    uploads = {"thread": None}
    _pull_and_save(args, _FakeAverager(tree, 7), 7,
                   lambda path, step: uploaded.append(path), uploads)
    uploads["thread"].join(timeout=10)

    out = str(tmp_path / "out")
    with open(os.path.join(out, "checkpoint-7", "manifest.bin"), "rb") as f:
        manifest = CheckpointManifest.from_bytes(f.read())
    assert manifest.step == 7 and manifest.num_shards == 3  # ceil(11/4)
    store = ShardStore(os.path.join(out, "sharded"))
    assert store.manifest_steps() == [7]
    assert store.missing_shards(manifest) == []
    # the uploaded checkpoint dir carries the manifest next to state.bin
    assert os.path.isfile(os.path.join(uploaded[0], "manifest.bin"))


def test_coordinator_publishes_to_hub(tmp_path, monkeypatch):
    """End-to-end: a sharing trainer peer + coordinator loop with
    upload_interval -> checkpoint lands in the hub mirror."""
    from dedloc_tpu.core.config import CollaborationArguments, parse_config
    from dedloc_tpu.roles import coordinator
    from dedloc_tpu.roles.common import build_dht
    from dedloc_tpu.roles.coordinator import (
        CoordinatorExtraArguments,
        run_coordinator,
    )
    from dedloc_tpu.roles.trainer import run_trainer
    import threading

    base = [
        "--dht.listen_host", "127.0.0.1",
        "--training.model_size", "tiny",
        "--training.seq_length", "64",
        "--training.per_device_batch_size", "2",
        "--training.gradient_accumulation_steps", "2",
        "--training.warmup_steps", "2",
        "--training.total_steps", "50",
        "--averager.averaging_expiration", "1.0",
        "--averager.min_refresh_period", "0.1",
        "--averager.default_refresh_period", "0.3",
        "--optimizer.target_batch_size", "8",
    ]
    root_args = parse_config(
        CollaborationArguments,
        base + ["--training.output_dir", str(tmp_path / "coord")],
    )
    root_dht, _ = build_dht(root_args)
    try:
        addr = root_dht.get_visible_address()
        trainer_args = parse_config(
            CollaborationArguments,
            base + [
                "--dht.initial_peers", addr,
                "--training.max_local_steps", "40",
                "--training.save_steps", "0",
                "--training.output_dir", str(tmp_path / "peer"),
            ],
        )
        t = threading.Thread(target=run_trainer, args=(trainer_args,), daemon=True)
        t.start()

        mirror = str(tmp_path / "hub")

        def published():
            return [
                d for d in (os.listdir(mirror) if os.path.isdir(mirror) else [])
                if d.startswith("checkpoint-")
            ]

        # the loop's one way out before ``max_iterations`` (75 s): its next
        # refresh after the first checkpoint has landed leaves through the
        # role's own ``finally``
        class Published(Exception):
            pass

        def fetch_until_published(*args, **kwargs):
            if published():
                raise Published
            return fetch_metrics(*args, **kwargs)

        fetch_metrics = coordinator.fetch_metrics
        monkeypatch.setattr(coordinator, "fetch_metrics", fetch_until_published)
        coord_args = parse_config(
            CollaborationArguments,
            base + [
                "--dht.initial_peers", addr,
                "--training.output_dir", str(tmp_path / "coord"),
            ],
        )
        try:
            run_coordinator(
                coord_args,
                CoordinatorExtraArguments(
                    refresh_period=0.5,
                    upload_interval=0.1,
                    metrics_log_path=str(tmp_path / "metrics.jsonl"),
                    hub_mirror_dir=mirror,
                ),
                max_iterations=150,
            )
        except Published:
            pass
        t.join(timeout=60)
        assert published(), "coordinator never published a checkpoint to the hub"
    finally:
        root_dht.shutdown()


def test_git_uploader_survives_coordinator_restart(tmp_path):
    """A fresh work_dir against a hub remote with history must fetch and
    build on the remote tip — not fail every push as non-fast-forward."""
    remote = str(tmp_path / "hub.git")
    subprocess.run(
        ["git", "init", "--bare", "--initial-branch", "main", remote],
        check=True, capture_output=True,
    )
    git_hub_uploader(str(tmp_path / "work1"), remote)(_ckpt(tmp_path, 5, 1.0), 5)
    # restart: new working dir, same remote
    git_hub_uploader(str(tmp_path / "work2"), remote)(_ckpt(tmp_path, 9, 2.0), 9)
    log = subprocess.run(
        ["git", "-C", remote, "log", "--format=%s", "main"],
        check=True, capture_output=True, text=True,
    ).stdout.strip().splitlines()
    assert log == [
        "checkpoint at collaboration step 9",
        "checkpoint at collaboration step 5",
    ]
