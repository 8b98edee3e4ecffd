"""Launcher CLI (multiprocess on localhost, ref test_launch.sh pattern) and
auto-checkpoint epoch resume (ref test_auto_checkpoint*.py)."""
import json
import os
import sys
import textwrap

import numpy as np
import pytest

from paddle_tpu.distributed.launch import launch
from paddle_tpu.utils import AutoCheckpoint


def _worker_script(tmp_path, body):
    p = tmp_path / "worker.py"
    p.write_text(textwrap.dedent(body))
    return str(p)


def test_launch_sets_trainer_env_and_collects_all(tmp_path):
    out_dir = tmp_path / "out"
    out_dir.mkdir()
    script = _worker_script(tmp_path, f"""
        import json, os
        rank = os.environ["PADDLE_TRAINER_ID"]
        info = {{
            "rank": int(rank),
            "num": int(os.environ["PADDLE_TRAINERS_NUM"]),
            "endpoints": os.environ["PADDLE_TRAINER_ENDPOINTS"],
            "current": os.environ["PADDLE_CURRENT_ENDPOINT"],
        }}
        with open(os.path.join({str(out_dir)!r}, f"r{{rank}}.json"), "w") as f:
            json.dump(info, f)
    """)
    rc = launch(script, [], nproc=3, log_dir=str(tmp_path / "logs"))
    assert rc == 0
    infos = []
    for r in range(3):
        with open(out_dir / f"r{r}.json") as f:
            infos.append(json.load(f))
    assert [i["rank"] for i in infos] == [0, 1, 2]
    assert all(i["num"] == 3 for i in infos)
    eps = infos[0]["endpoints"].split(",")
    assert len(eps) == 3 and infos[1]["current"] == eps[1]
    # logs captured per worker
    assert (tmp_path / "logs" / "worker.0.log").exists()


def test_launch_refuses_multiprocess_on_tpu_host(tmp_path, monkeypatch):
    """A chip belongs to one process: nproc>1 where the workers would take
    the TPU is refused before anything is spawned; a CPU environment is
    answered from JAX_PLATFORMS alone (no probe child)."""
    from paddle_tpu.distributed import launch as launch_mod

    assert not launch_mod._workers_take_tpu({"JAX_PLATFORMS": "cpu"})
    marker = tmp_path / "ran.txt"
    script = _worker_script(tmp_path, f"""
        open({str(marker)!r}, "w").write("ran")
    """)
    monkeypatch.setattr(launch_mod, "_workers_take_tpu", lambda env: True)
    with pytest.raises(RuntimeError, match="one process"):
        launch(script, [], nproc=2)
    assert not marker.exists()
    assert launch(script, [], nproc=1) == 0   # one process per host is fine
    assert marker.exists()


def test_launch_propagates_failure_and_kills_peers(tmp_path):
    marker = tmp_path / "late.txt"
    script = _worker_script(tmp_path, f"""
        import os, sys, time
        if os.environ["PADDLE_TRAINER_ID"] == "1":
            sys.exit(7)          # fast failure
        time.sleep(30)           # peer would run long; must be terminated
        open({str(marker)!r}, "w").write("survived")
    """)
    import time
    t0 = time.monotonic()
    rc = launch(script, [], nproc=2)
    elapsed = time.monotonic() - t0
    assert rc == 7
    assert elapsed < 15, "peer was not killed promptly"
    assert not marker.exists()


def test_auto_checkpoint_resume_cycle(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    run1_epochs = []
    acp = AutoCheckpoint(ckpt, job_id="job1", keep_last=2)
    assert acp.last_epoch == -1
    for epoch in acp.train_epoch_range(5):
        state = {"w": np.full(3, float(epoch)), "epoch": np.asarray(epoch)}
        acp.save(epoch, state)
        run1_epochs.append(epoch)
        if epoch == 2:
            break  # simulated preemption
    assert run1_epochs == [0, 1, 2]

    # relaunch: resumes after epoch 2 with the saved state available
    acp2 = AutoCheckpoint(ckpt, job_id="job1")
    assert acp2.last_epoch == 2
    resumed = list(acp2.train_epoch_range(5))
    assert resumed == [3, 4]
    np.testing.assert_allclose(acp2.restored_state["w"], 2.0)


def test_auto_checkpoint_gc_keeps_last(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    acp = AutoCheckpoint(ckpt, job_id="j", keep_last=2)
    for epoch in range(4):
        acp.save(epoch, {"e": np.asarray(epoch)})
    names = sorted(os.listdir(os.path.join(ckpt, "j")))
    # keep_last=2: newest (3) plus one prior (2) survive
    assert "epoch_3" in names and "epoch_2" in names
    assert "epoch_0" not in names and "epoch_1" not in names


def test_auto_checkpoint_missing_snapshot_fails_loudly(tmp_path):
    import shutil
    ckpt = str(tmp_path / "ckpt")
    acp = AutoCheckpoint(ckpt, job_id="j")
    acp.save(0, {"x": np.zeros(1)})
    shutil.rmtree(os.path.join(ckpt, "j", "epoch_0"))  # partial loss
    acp2 = AutoCheckpoint(ckpt, job_id="j")
    with pytest.raises(RuntimeError, match="could not be loaded"):
        list(acp2.train_epoch_range(3))


def test_auto_checkpoint_different_jobs_isolated(tmp_path):
    ckpt = str(tmp_path / "ckpt")
    a = AutoCheckpoint(ckpt, job_id="a")
    a.save(0, {"x": np.zeros(1)})
    b = AutoCheckpoint(ckpt, job_id="b")
    assert b.last_epoch == -1
