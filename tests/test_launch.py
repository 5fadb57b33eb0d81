"""Launcher tests — both reference entry styles (SURVEY.md §3.1/§3.2) on
real OS processes, each with its own 1-device CPU sim: the "multi-node
without a cluster" rig the reference never had (§4 item 4).
"""

import os
import subprocess
import sys
import textwrap

import pytest

from pytorchdistributed_tpu.runtime.launch import launch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _allgather_worker(rank):
    # runs in a fresh spawned process: set up its own platform
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, REPO)
    from pytorchdistributed_tpu.runtime import dist

    dist.init_process_group()
    assert dist.get_rank() == rank
    assert dist.get_world_size() == 2
    import jax.numpy as jnp
    from jax.experimental import multihost_utils

    got = multihost_utils.process_allgather(jnp.array([dist.get_rank()]))
    assert got.ravel().tolist() == [0, 1]
    dist.destroy_process_group()


def _failing_worker(rank):
    if rank == 1:
        raise SystemExit(3)


def _hang_or_fail_worker(rank):
    if rank == 1:
        raise SystemExit(5)
    import time
    time.sleep(600)  # rank 0 blocks (e.g. in a collective) forever


def test_spawn_style_collective():
    """The mp.spawn path (reference ddp_gpus.py:98): 2 processes rendezvous
    via the env contract and complete a cross-process collective."""
    launch(_allgather_worker, 2, devices_per_proc=1, timeout=180)


def test_spawn_style_failure_propagates():
    with pytest.raises(RuntimeError, match="rank 1 failed"):
        launch(_failing_worker, 2, devices_per_proc=1, timeout=60)


def test_spawn_style_fail_fast_with_blocked_earlier_rank():
    """A later rank's crash must tear the group down even while an earlier
    rank is blocked (the sequential-join hang: rank 0 stuck in a collective
    waiting for dead rank 1). Must fail in seconds, not at rank 0's
    600s sleep."""
    import time
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="rank 1 failed"):
        launch(_hang_or_fail_worker, 2, devices_per_proc=1, timeout=None)
    assert time.monotonic() - t0 < 60


def test_sim_device_flags_deduplicated():
    """Inherited XLA_FLAGS with a device count must be replaced, not
    appended (last-flag-wins is brittle)."""
    from pytorchdistributed_tpu.runtime.launch import sim_device_flags
    out = sim_device_flags(
        "--foo=1 --xla_force_host_platform_device_count=8 --bar=2", 4)
    assert out.count("xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=4" in out
    assert "--foo=1" in out and "--bar=2" in out


def test_torchrun_style_cli(tmp_path):
    """The torchrun path (reference ddp_gpus_torchrun.py:102): the run CLI
    sets the env contract; the script reads it via init_process_group."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.path.insert(0, {REPO!r})
        from pytorchdistributed_tpu.runtime import dist
        dist.init_process_group()
        rank = dist.get_rank()
        assert os.environ["RANK"] == str(rank)
        assert dist.get_world_size() == 2
        dist.barrier("test")
        dist.destroy_process_group()
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--devices-per-proc", "1", str(script)],
        cwd=REPO, timeout=240, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr


def test_heartbeat_detects_hung_rank(tmp_path):
    """Hung-rank fault injection (VERDICT r2 missing #1): rank 1 wedges
    itself (SIGSTOP — alive, silent, never exits), once before its first
    beat and once after, covering both staleness clocks: the pre-first-beat
    ``grace`` window (nothing is stamped at construction, by design — the
    first XLA compile must not count against ``timeout``) and the
    post-beat ``timeout``. Exit-watching alone would hang forever; the
    watchdog must flag the rank, tear the group down (SIGCONT+TERM wakes
    the frozen worker) and relaunch until the third incarnation completes."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, signal, sys, time
        sys.path.insert(0, {REPO!r})
        from pytorchdistributed_tpu.runtime.heartbeat import Heartbeat

        hb = Heartbeat.from_env()
        assert hb is not None, "launcher did not export PTD_HEARTBEAT_DIR"
        tmp = {str(tmp_path)!r}
        if os.environ["RANK"] == "1":
            if not os.path.exists(os.path.join(tmp, "froze_early")):
                open(os.path.join(tmp, "froze_early"), "w").close()
                os.kill(os.getpid(), signal.SIGSTOP)   # before first beat
            elif not os.path.exists(os.path.join(tmp, "froze_late")):
                open(os.path.join(tmp, "froze_late"), "w").close()
                hb.beat()
                os.kill(os.getpid(), signal.SIGSTOP)   # after first beat
        for _ in range(8):   # healthy ranks keep beating to completion
            hb.beat()
            time.sleep(0.1)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--max-restarts", "2",
         "--heartbeat-timeout", "2.0", "--heartbeat-grace", "8.0",
         "--monitor-interval", "0.1", str(script)],
        cwd=REPO, timeout=180, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "hung (heartbeat stale)" in proc.stderr, proc.stderr
    assert "restart 1/2" in proc.stderr and "restart 2/2" in proc.stderr


def test_heartbeat_ignores_cleanly_exited_ranks(tmp_path):
    """A rank that finishes early stops beating legitimately; the agent
    must not flag it as hung while the rest of the group keeps working."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys, time
        sys.path.insert(0, {REPO!r})
        from pytorchdistributed_tpu.runtime.heartbeat import Heartbeat

        hb = Heartbeat.from_env()
        if os.environ["RANK"] == "0":
            sys.exit(0)          # done immediately, no more beats
        for _ in range(30):      # rank 1 outlives the timeout by 2x
            hb.beat()
            time.sleep(0.1)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--heartbeat-timeout", "1.0",
         "--monitor-interval", "0.1", str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "hung" not in proc.stderr, proc.stderr


def test_torchrun_style_elastic_restart(tmp_path):
    """Fault injection (SURVEY.md §5): rank 0 dies on the first incarnation,
    the agent relaunches the group, second incarnation succeeds."""
    marker = tmp_path / "died_once"
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys
        marker = {str(marker)!r}
        if os.environ["RANK"] == "0" and not os.path.exists(marker):
            open(marker, "w").close()
            sys.exit(17)  # simulated failure, pre-rendezvous
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "2", "--max-restarts", "1", str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "restart 1/1" in proc.stderr


def test_elastic_restart_resumes_real_training(tmp_path):
    """The launcher's restart-resume promise, end to end (VERDICT r4 #5 /
    weak #4 — every other launcher test uses synthetic exit-code workers):
    a REAL 2-process DDP training job checkpoints as it goes, rank 0 kills
    itself mid-epoch-1, the agent relaunches the group, and the second
    incarnation's ``fit(resume=True)`` restores the sharded checkpoint and
    fast-forwards to where it left off. The resumed run's final loss must
    equal an uninterrupted run's exactly (same data order via
    set_epoch+skip_steps, same per-step rng folded from state.step) —
    restart-from-checkpoint semantics, SURVEY.md §5."""
    import json

    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import json, os, sys
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
        sys.path.insert(0, {REPO!r})
        import optax
        from pytorchdistributed_tpu.data import (
            DataLoader, SyntheticRegressionDataset)
        from pytorchdistributed_tpu.models import MLP
        from pytorchdistributed_tpu.runtime import dist
        from pytorchdistributed_tpu.runtime.mesh import create_mesh
        from pytorchdistributed_tpu.training import Trainer, mse_loss

        dist.init_process_group()
        marker = os.environ["PTD_TEST_MARKER"]  # "" = uninterrupted run

        class KillAfter:
            # mid-epoch fault injection: rank 0 dies right before its
            # (n+1)-th batch, once (the marker survives the relaunch)
            def __init__(self, loader, n):
                self.loader, self.n = loader, n

            def __len__(self):
                return len(self.loader)

            def __getattr__(self, name):
                return getattr(self.loader, name)

            def set_epoch(self, epoch):
                self.loader.set_epoch(epoch)

            def __iter__(self):
                for batch in self.loader:
                    if (marker and dist.get_rank() == 0
                            and not os.path.exists(marker)):
                        if self.n == 0:
                            open(marker, "w").close()
                            os._exit(17)
                        self.n -= 1
                    yield batch

        ds = SyntheticRegressionDataset(size=64, in_dim=8, out_dim=1,
                                        seed=0)
        loader = DataLoader(ds, batch_size=8,
                            num_replicas=dist.get_world_size(),
                            rank=dist.get_rank())
        tr = Trainer(MLP(features=(16, 1)), optax.sgd(0.05), mse_loss,
                     mesh=create_mesh(),
                     checkpoint_dir=os.environ["PTD_TEST_CKPT"],
                     checkpoint_every_steps=2, log_every=10**9,
                     watchdog=False)
        # 4 steps/epoch (64 / (8 x 2 ranks)): die at epoch 1 step 2, past
        # the epoch-0 end save and the step-6 periodic save
        metrics = tr.fit(KillAfter(loader, 6) if marker else loader,
                         max_epochs=2, resume=True)
        if dist.get_rank() == 0:
            with open(os.environ["PTD_TEST_OUT"], "w") as f:
                json.dump(metrics, f)
        dist.destroy_process_group()
    """))

    def run(tag, *, kill):
        out = tmp_path / f"{tag}.json"
        env = dict(
            os.environ,
            PTD_TEST_CKPT=str(tmp_path / f"ckpt_{tag}"),
            PTD_TEST_OUT=str(out),
            PTD_TEST_MARKER=str(tmp_path / "died_once") if kill else "",
        )
        proc = subprocess.run(
            [sys.executable, "-m", "pytorchdistributed_tpu.run",
             "--nproc-per-node", "2", "--devices-per-proc", "1",
             "--max-restarts", "1", "--monitor-interval", "0.1",
             str(script)],
            cwd=REPO, timeout=600, capture_output=True, text=True, env=env,
        )
        assert proc.returncode == 0, proc.stderr
        return proc, json.loads(out.read_text())

    proc, interrupted = run("killed", kill=True)
    assert "restart 1/1" in proc.stderr, proc.stderr
    # resume really ran (trainer logs land on the worker's stdout, which
    # the agent inherits)
    assert "resumed from step" in proc.stdout, (proc.stdout, proc.stderr)
    _, baseline = run("clean", kill=False)
    assert interrupted["loss"] == pytest.approx(baseline["loss"],
                                                rel=1e-6), (
        interrupted, baseline)


def test_elastic_resize_drops_persistently_bad_rank(tmp_path):
    """torchrun --nnodes=min:max resize semantics (--elastic-min-nproc,
    VERDICT r3 missing #3 stretch): the top rank fails whenever the group
    is larger than 2 — a persistently bad slot. After it fails twice in a
    row the agent relaunches the group one smaller instead of burning the
    remaining restarts; the 2-wide incarnation completes."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        world = int(os.environ["WORLD_SIZE"])
        if world > 2 and os.environ["RANK"] == str(world - 1):
            sys.exit(13)
    """))
    # max-restarts 1 also proves the shrink is NOT charged to the restart
    # budget: fail -> restart 1/1 -> fail again -> resize (free) -> done
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "3", "--max-restarts", "1",
         "--elastic-min-nproc", "2", "--monitor-interval", "0.1",
         str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "resizing group to 2 (elastic)" in proc.stderr, proc.stderr
    # with resize disabled, the same failure exhausts the restarts
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "3", "--max-restarts", "2",
         "--monitor-interval", "0.1", str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert "no restarts left" in proc.stderr


def test_elastic_shrink_then_regrow(tmp_path):
    """torchrun's max bound is standing, not a ratchet (VERDICT r4 missing
    #3): after a shrink, a charged relaunch boundary whose incarnation
    first ran healthy past --elastic-regrow-after probes one worker
    bigger. Scenario: the top rank fails fast while 3-wide but only twice
    (a transient bad slot) → shrink to 2; the 2-wide group runs stably,
    then rank 0 hits a one-off failure — that restart regrows to 3; the
    now-healthy 3-wide group completes."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, sys, time
        tmp = {str(tmp_path)!r}
        world = int(os.environ["WORLD_SIZE"])
        # top rank is bad while 3-wide, but only for its first two lives
        # (fails FAST — must not look like a stable group to the probe)
        fails = os.path.join(tmp, "topfails")
        n = (len(open(fails).read().splitlines())
             if os.path.exists(fails) else 0)
        if world > 2 and os.environ["RANK"] == str(world - 1) and n < 2:
            with open(fails, "a") as f:
                f.write("x\\n")
            sys.exit(13)
        # everyone else works for a while (past the regrow-after gate)
        time.sleep(1.5)
        # one transient rank-0 failure at the shrunken size AFTER the
        # stable stretch: the restart it forces carries the regrow probe
        transient = os.path.join(tmp, "transient")
        if (world == 2 and os.environ["RANK"] == "0"
                and not os.path.exists(transient)):
            open(transient, "w").close()
            sys.exit(11)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "3", "--max-restarts", "2",
         "--elastic-min-nproc", "2", "--elastic-regrow-after", "1.0",
         "--monitor-interval", "0.1", str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "resizing group to 2 (elastic)" in proc.stderr, proc.stderr
    assert "regrowing group to 3" in proc.stderr, proc.stderr
    # order: shrink first, then the regrow probe
    assert (proc.stderr.index("resizing group to 2")
            < proc.stderr.index("regrowing group to 3")), proc.stderr


def test_elastic_regrow_gate_lets_shrink_reach_min(tmp_path):
    """The uptime gate that keeps regrow from fighting shrink: a slot
    that's bad whenever the group is wider than 2 fails FAST, so no
    restart ever probes bigger, shrink evidence accumulates undisturbed,
    and a 4-wide job steps 4 → 3 → 2 and completes — sizes below max−1
    must stay reachable (a probe on every restart would reset the
    tracker first and flap 4↔3 until the budget died)."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent("""
        import os, sys
        world = int(os.environ["WORLD_SIZE"])
        if world > 2 and os.environ["RANK"] == str(world - 1):
            sys.exit(13)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "4", "--max-restarts", "2",
         "--elastic-min-nproc", "2", "--monitor-interval", "0.1",
         str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "resizing group to 3 (elastic)" in proc.stderr, proc.stderr
    assert "resizing group to 2 (elastic)" in proc.stderr, proc.stderr
    assert "regrowing" not in proc.stderr, proc.stderr


def test_elastic_regrow_gate_ignores_hung_detection_latency(tmp_path):
    """A slot that persistently WEDGES (never exits, never beats) must not
    pass the regrow gate on detection latency: heartbeat grace/timeout is
    time spent *discovering* the hang, not healthy runtime, so the gate
    credits a hung cohort only up to its last observed beat (0 here — it
    never beat) and the shrink still reaches the healthy size."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(f"""
        import os, signal, sys, time
        sys.path.insert(0, {REPO!r})
        from pytorchdistributed_tpu.runtime.heartbeat import Heartbeat
        world = int(os.environ["WORLD_SIZE"])
        if world > 2 and os.environ["RANK"] == str(world - 1):
            os.kill(os.getpid(), signal.SIGSTOP)   # wedge, never beat
        hb = Heartbeat.from_env()
        for _ in range(5):
            hb.beat()
            time.sleep(0.1)
    """))
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "3", "--max-restarts", "1",
         "--elastic-min-nproc", "2", "--elastic-regrow-after", "1.0",
         # generous grace: healthy ranks must land their FIRST beat
         # inside it even when the whole suite is hammering one core
         # (8.0 flaked there — imports alone can exceed it under load)
         "--heartbeat-timeout", "4.0", "--heartbeat-grace", "20.0",
         "--monitor-interval", "0.1", str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr
    assert "resizing group to 2 (elastic)" in proc.stderr, proc.stderr
    assert "regrowing" not in proc.stderr, proc.stderr


def test_elastic_resize_ignores_group_wide_failures(tmp_path):
    """A failure that takes out EVERY rank (bad script arg analog) is no
    evidence of one bad slot: the tracker resets, no shrink happens, and
    the restarts budget is what runs out."""
    script = tmp_path / "worker.py"
    script.write_text("import sys; sys.exit(7)\n")
    proc = subprocess.run(
        [sys.executable, "-m", "pytorchdistributed_tpu.run",
         "--nproc-per-node", "3", "--max-restarts", "2",
         "--elastic-min-nproc", "2", "--monitor-interval", "0.1",
         str(script)],
        cwd=REPO, timeout=120, capture_output=True, text=True,
    )
    assert proc.returncode == 1
    assert "resizing" not in proc.stderr, proc.stderr
    assert "no restarts left" in proc.stderr


def test_stale_ranks_clocks(tmp_path):
    """Unit check of the agent's two staleness clocks: a rank WITH a beat
    file is judged by `timeout` from its mtime; a rank with NO file (still
    importing / compiling) gets the more generous `grace` from spawn."""
    import os

    from pytorchdistributed_tpu.runtime.heartbeat import stale_ranks

    spawn = 1000.0
    (tmp_path / "rank0").touch()
    os.utime(tmp_path / "rank0", times=(spawn + 5, spawn + 5))
    # rank1 never beat (no file)
    kw = dict(timeout=2.0, grace=30.0, baseline=spawn)
    # t=6: rank0 fresh (beat at +5), rank1 inside grace
    assert stale_ranks(tmp_path, 2, now=spawn + 6, **kw) == []
    # t=8: rank0 stale (3s > timeout), rank1 still inside grace
    assert stale_ranks(tmp_path, 2, now=spawn + 8, **kw) == [0]
    # t=31: rank1 exceeded grace too
    assert stale_ranks(tmp_path, 2, now=spawn + 31, **kw) == [0, 1]
    # a fresh incarnation's baseline resets both clocks (stale old mtimes
    # are ignored via max(mtime, baseline))
    assert stale_ranks(tmp_path, 2, timeout=2.0, grace=30.0,
                       now=spawn + 100, baseline=spawn + 99) == []
