"""torchrun-style launcher CLI with elastic restart.

    python -m pytorchdistributed_tpu.run --nproc-per-node 2 train.py --lr 3e-4

The agent process (this module) spawns one worker per rank with the env
contract the reference's scripts read (RANK / WORLD_SIZE / LOCAL_RANK /
MASTER_ADDR / MASTER_PORT — reference ddp_gpus_torchrun.py:14-19), watches
for failures, and on ``--max-restarts > 0`` tears the group down and
relaunches it — restart-from-checkpoint semantics (workers are expected to
resume via Trainer.fit(resume=True); SURVEY.md §5 "Failure detection /
elastic recovery").

``--heartbeat-timeout T`` adds *hung*-rank detection on top of exit
watching: a rank wedged in a collective (the NCCL-deadlock analog) never
exits, so the agent also tracks per-rank liveness files
(runtime/heartbeat.py; the Trainer beats at its device-sync points) and
treats a rank silent for more than T seconds as failed — kill the group,
relaunch if restarts remain.

``--elastic-min-nproc M`` enables torchrun's ``--nnodes=min:max`` resize
semantics (beyond the reference, which pins ``--nproc_per_node=2``,
ddp_gpus_torchrun.py:102): when the SAME single rank fails twice
consecutively, the group relaunches one worker smaller (never below M)
and ranks renumber — capacity reduction so training continues, NOT
slot exclusion (this launcher assigns no fixed hardware to a rank; a
failure tied to the rank NUMBER itself would move with the renumbering).
Shrinks are bounded by ``nproc − M`` and are not charged against
``--max-restarts``; group-wide failures (more than one nonzero exit, e.g.
a bad script arg) reset the per-rank tracker and only consume restarts.
Observing a repeat takes one same-size relaunch, so the flag needs
``--max-restarts ≥ 1`` to ever fire. Workers read the new WORLD_SIZE from
the env contract and re-shard their data accordingly; note the Trainer's
mid-epoch resume geometry guard refuses to fast-forward across a
world-size change (resume restarts the epoch boundary from the
checkpoint instead).

A shrunken group does not stay shrunken for the life of the job
(torchrun's max bound is standing, not a ratchet): a charged relaunch
boundary after a shrink probes one worker BIGGER again, back toward the
original ``--nproc-per-node`` — but only when the incarnation that just
failed had first run HEALTHY for ``--elastic-regrow-after`` seconds.
The uptime gate is what separates "stable group hit an independent
transient, worth probing for returned capacity" from "still failing
fast, the shrink evidence is not done accumulating": without it a
probe on every restart would reset the consecutive-failure tracker
before it ever reached two, making sizes below max−1 unreachable for a
persistently bad slot. Probes ride restarts the group was paying for
anyway, so flapping is bounded by the ``--max-restarts`` budget. There
is no external "node joined" signal on a single-host agent (torchrun
regrows on rendezvous arrivals), so a stable-then-interrupted relaunch
boundary is the honest stand-in.

Preemption + chaos (SURVEY.md §5 completion): a SIGTERM/SIGINT received
by the agent is FORWARDED to the workers, whose Trainers drain a durable
checkpoint and exit ``EXIT_PREEMPTED`` within ``--preempt-grace`` seconds
— Ctrl-C never orphans a group. A worker exiting ``EXIT_PREEMPTED`` on
its own (the platform preempted one VM, or an injected ``preempt@step``)
is restarted but never charged to the same-rank tracker above: reclaimed
capacity is not evidence of a bad slot. ``--faults`` exports a
deterministic fault-injection spec (``PTD_FAULTS``; see faults/inject.py)
plus a marker directory (``PTD_FAULTS_STATE``) that keeps step-targeted
faults one-shot across relaunches — the chaos-suite rig every
fault-tolerance claim in this repo is tested through.
"""

from __future__ import annotations

import argparse
import os
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time

from pytorchdistributed_tpu.faults.inject import (
    EXIT_PREEMPTED,
    FAULTS_ENV,
    FAULTS_STATE_ENV,
    FaultPlan,
)
from pytorchdistributed_tpu.runtime.heartbeat import (
    HEARTBEAT_DIR_ENV,
    stale_ranks,
)
from pytorchdistributed_tpu.telemetry.events import (
    TELEMETRY_DIR_ENV,
    summarize_new_events,
)


def free_port() -> int:
    """An OS-assigned free localhost port (the MASTER_PORT of the env
    contract). Public: the serving replica router's subprocess mode
    reuses the same rendezvous contract for its workers."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn_group(argv, nproc: int, port: int,
                 devices_per_proc: int | None,
                 heartbeat_dir: str | None = None,
                 telemetry_dir: str | None = None,
                 extra_env: dict[str, str] | None = None,
                 ) -> list[subprocess.Popen]:
    from pytorchdistributed_tpu.runtime.launch import worker_envs

    # one process for each chip: on a TPU host worker r is shown chip r
    # only (RuntimeError, before anything is spawned, when the chips
    # cannot back the group one-to-one)
    procs = []
    for worker in worker_envs(nproc, port, devices_per_proc):
        env = dict(os.environ)
        if extra_env:
            env.update(extra_env)
        env.update(worker)
        if heartbeat_dir is not None:
            env[HEARTBEAT_DIR_ENV] = heartbeat_dir
        if telemetry_dir is not None:
            env[TELEMETRY_DIR_ENV] = telemetry_dir
        procs.append(subprocess.Popen([sys.executable] + argv, env=env))
    return procs


def kill_group(procs, *, sig: int = signal.SIGTERM,
               grace: float = 10.0) -> None:
    """Signal every live worker and SIGKILL stragglers after ``grace``
    seconds. The default (SIGTERM, 10 s) is the failure-teardown path; the
    agent's signal forwarding reuses it with the received signal and
    ``--preempt-grace`` so Trainers get one window to drain durable
    checkpoints — one escalation point, not two. Public: the serving
    replica router's subprocess teardown uses the same escalation so a
    drained router can never leave an orphan replica worker."""
    for p in procs:
        if p.poll() is None:
            p.send_signal(sig)
            # a SIGSTOPped (hung-and-frozen) worker can't handle SIGTERM;
            # wake it so termination isn't stuck behind the escalation
            p.send_signal(signal.SIGCONT)
    deadline = time.time() + max(grace, 0.1)
    for p in procs:
        try:
            p.wait(max(0.1, deadline - time.time()))
        except subprocess.TimeoutExpired:
            p.kill()


def _forward_signal_and_drain(procs, signum: int, grace: float) -> None:
    """Agent received SIGTERM/SIGINT: forward it to every live worker —
    Ctrl-C must not orphan the group, and a platform preemption notice
    must reach the Trainers (SIGINT is translated to SIGTERM, the signal
    their preemption handler owns)."""
    fwd = signal.SIGTERM if signum == signal.SIGINT else signum
    kill_group(procs, sig=fwd, grace=grace)


def main(argv=None) -> int:
    owned_dirs: list[str] = []
    try:
        return _main(argv, owned_dirs)
    finally:
        for d in owned_dirs:
            shutil.rmtree(d, ignore_errors=True)


def _main(argv, owned_dirs: list[str]) -> int:
    parser = argparse.ArgumentParser(
        "pytorchdistributed_tpu.run",
        description="torchrun-equivalent launcher "
                    "(reference ddp_gpus_torchrun.py:102)")
    parser.add_argument("--nproc-per-node", type=int, default=1)
    parser.add_argument("--max-restarts", type=int, default=0,
                        help="relaunch the whole group this many times if a "
                             "rank fails (workers resume from checkpoints)")
    parser.add_argument("--monitor-interval", type=float, default=0.2)
    parser.add_argument("--heartbeat-timeout", type=float, default=0.0,
                        help="seconds of per-rank heartbeat silence before "
                             "the group counts as hung and is relaunched "
                             "(0 = exit-watching only)")
    parser.add_argument("--heartbeat-grace", type=float, default=300.0,
                        help="extra allowance before a rank's FIRST beat "
                             "(imports + first XLA compile)")
    parser.add_argument("--devices-per-proc", type=int, default=None,
                        help="CPU-sim chips per process (sets JAX_PLATFORMS="
                             "cpu + xla_force_host_platform_device_count)")
    parser.add_argument("--telemetry-dir", type=str, default=None,
                        help="run directory for the unified telemetry "
                             "subsystem: exported to workers as "
                             f"{TELEMETRY_DIR_ENV} (Trainers write spans/"
                             "metrics/events per rank there) and the agent "
                             "prints each incarnation's tripwire events "
                             "next to its restart decisions; read back "
                             "with `python -m pytorchdistributed_tpu."
                             "telemetry report <dir>`")
    parser.add_argument("--elastic-min-nproc", type=int, default=0,
                        help="allow the group to relaunch SMALLER (down to "
                             "this size) when the same rank fails twice in "
                             "a row, and to probe back BIGGER (up to "
                             "--nproc-per-node) on later restarts — "
                             "torchrun --nnodes=min:max resize semantics "
                             "(0 = fixed size)")
    parser.add_argument("--preempt-grace", type=float, default=30.0,
                        help="seconds workers get to drain a graceful "
                             "checkpoint after the agent forwards a "
                             "SIGTERM/SIGINT it received, before the "
                             "escalating teardown")
    parser.add_argument("--faults", type=str, default=None,
                        help="deterministic fault-injection spec exported "
                             f"to workers as {FAULTS_ENV} (e.g. "
                             "'crash@step=7,rank=1; nan@step=9; "
                             "preempt@step=15'); one-shot markers persist "
                             f"across relaunches via {FAULTS_STATE_ENV}")
    parser.add_argument("--elastic-regrow-after", type=float, default=30.0,
                        help="minimum healthy uptime (s) of the failing "
                             "incarnation before a restart also probes the "
                             "group one worker bigger; failures earlier "
                             "than this are treated as continuing "
                             "instability and never regrow")
    parser.add_argument("script", help="training script to run")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    worker_argv = [args.script] + args.script_args
    restarts = 0
    nproc = args.nproc_per_node
    last_failed, consecutive = None, 0
    if args.telemetry_dir is not None:
        os.makedirs(args.telemetry_dir, exist_ok=True)
    # Fault-injection contract: --faults (or an inherited PTD_FAULTS)
    # reaches workers through their spawn environment — never by
    # mutating the agent's own os.environ, which would leak specs into
    # later in-process main() calls and unrelated subprocesses. The
    # agent provisions ONE marker directory for the whole run so
    # step-targeted faults stay one-shot across relaunches (a crash@step
    # spec that re-fired every incarnation would be an infinite crash
    # loop, not a test).
    faults_env: dict[str, str] = {}
    if args.faults:
        FaultPlan.parse(args.faults)  # fail fast on a typo'd spec
        faults_env[FAULTS_ENV] = args.faults
    if ((args.faults or os.environ.get(FAULTS_ENV))
            and not os.environ.get(FAULTS_STATE_ENV)):
        state_dir = tempfile.mkdtemp(prefix="ptd_faults_")
        faults_env[FAULTS_STATE_ENV] = state_dir
        owned_dirs.append(state_dir)
    # Signal forwarding (graceful teardown / preemption notice): the
    # handler only records the signal — forwarding and the grace wait
    # happen in the monitor loop, outside async-signal context.
    signals_seen: list[int] = []
    for signum in (signal.SIGTERM, signal.SIGINT):
        signal.signal(signum, lambda s, f: signals_seen.append(s))
    # Per-incarnation telemetry aggregation: byte offsets into the
    # per-rank event files advance as the agent reports, so each summary
    # covers exactly the incarnation that just ended — the tripwire
    # analog of the heartbeat state printed on the same stream.
    tele_offsets: dict[str, int] = {}

    def report_telemetry() -> None:
        if args.telemetry_dir is None:
            return
        summary = summarize_new_events(args.telemetry_dir, tele_offsets)
        if summary is not None:
            print(f"[run] telemetry: {summary}", file=sys.stderr)
    if args.elastic_min_nproc > 0 and args.max_restarts < 1:
        print("[run] warning: --elastic-min-nproc needs --max-restarts >= 1 "
              "to observe a repeated failure; it will never fire",
              file=sys.stderr)
    while True:
        port = free_port()
        # fresh heartbeat dir per incarnation: a relaunch must not inherit
        # the dead group's file mtimes
        hb_dir = (tempfile.mkdtemp(prefix="ptd_heartbeat_")
                  if args.heartbeat_timeout > 0 else None)
        spawned_at = time.time()
        try:
            procs = _spawn_group(worker_argv, nproc, port,
                                 args.devices_per_proc, hb_dir,
                                 args.telemetry_dir, faults_env)
        except RuntimeError as e:  # chips cannot back the group: refuse
            print(f"[run] {e}", file=sys.stderr)
            return 2
        failed, why = [], "failed"
        while not failed:
            time.sleep(args.monitor_interval)
            if signals_seen:
                # graceful teardown: forward the signal so Trainers drain
                # durable checkpoints (never orphan workers on Ctrl-C)
                signum = signals_seen[0]
                print(f"[run] received {signal.Signals(signum).name}; "
                      f"forwarding to workers "
                      f"(grace {args.preempt_grace}s)", file=sys.stderr)
                _forward_signal_and_drain(procs, signum, args.preempt_grace)
                if hb_dir is not None:
                    shutil.rmtree(hb_dir, ignore_errors=True)
                report_telemetry()
                codes = [p.poll() for p in procs]
                if all(c == 0 for c in codes):
                    return 0
                if all(c in (0, EXIT_PREEMPTED) for c in codes):
                    print("[run] workers preempted gracefully "
                          "(checkpoints drained)", file=sys.stderr)
                    return EXIT_PREEMPTED
                return 128 + signum
            codes = [p.poll() for p in procs]
            suspect, why = [], "failed"
            if any(c not in (None, 0) for c in codes):
                suspect = [r for r, c in enumerate(codes)
                           if c not in (None, 0)]
            elif all(c == 0 for c in codes):
                if hb_dir is not None:
                    shutil.rmtree(hb_dir, ignore_errors=True)
                report_telemetry()
                return 0
            elif hb_dir is not None:
                hung = stale_ranks(hb_dir, nproc,
                                   timeout=args.heartbeat_timeout,
                                   grace=args.heartbeat_grace,
                                   now=time.time(), baseline=spawned_at)
                # only live ranks count as hung — a cleanly-exited rank
                # stops beating legitimately while the rest finish up
                hung = [r for r in hung if codes[r] is None]
                if hung:
                    suspect, why = hung, "hung (heartbeat stale)"
            if not suspect:
                continue
            # settle window before attributing single-vs-group: in a
            # group-wide crash (or group-wide collective wedge) the
            # siblings fail within moments of the first-seen member, and
            # sampling too early would misread it as one bad rank.
            # Floored at 0.5 s — monitor-interval alone can be shorter
            # than sibling skew.
            time.sleep(max(args.monitor_interval, 0.5))
            codes = [p.poll() for p in procs]
            if all(c == 0 for c in codes):
                # the suspects were finishing up (e.g. a slow final
                # checkpoint save outlived the heartbeat timeout) and the
                # whole group completed during the settle — success
                if hb_dir is not None:
                    shutil.rmtree(hb_dir, ignore_errors=True)
                report_telemetry()
                return 0
            exited = [r for r, c in enumerate(codes)
                      if c not in (None, 0)]
            if why == "failed":
                failed = exited  # nonzero codes are stable: non-empty
            else:
                # hung: the cohort is the still-live stale ranks PLUS any
                # sibling that crashed during the settle. Empty cohort =
                # false alarm (the stale rank exited 0 while siblings
                # keep working) — resume monitoring, nothing failed.
                stale = stale_ranks(hb_dir, nproc,
                                    timeout=args.heartbeat_timeout,
                                    grace=args.heartbeat_grace,
                                    now=time.time(), baseline=spawned_at)
                failed = sorted(set(r for r in stale if codes[r] is None)
                                | set(exited))
        # Snapshot BEFORE the teardown: kill_group can block ~10s on a
        # SIGTERM-ignoring worker, and that wait is not health either.
        detected_at = time.time()
        kill_group(procs)
        # aggregate this incarnation's tripwire events next to the
        # failure attribution below (NaN storms and loss spikes are the
        # why behind many a nonzero exit)
        report_telemetry()
        # Healthy uptime of the incarnation that just failed (feeds the
        # regrow gate below). Clean exits: wall clock to detection —
        # lag is ~monitor-interval + the settle window. HUNG cohorts:
        # detection latency (heartbeat grace/timeout, minutes by default)
        # is NOT health — credit the cohort only up to its last observed
        # beat, 0 if it never beat; otherwise a slot that persistently
        # WEDGES would pass the gate on pure detection lag and
        # regrow-flapping would defeat the shrink tracker (the exact
        # pathology the gate exists to prevent).
        if why == "failed":
            healthy_for = detected_at - spawned_at
        else:
            beats = []
            for r in failed:
                try:
                    beats.append(os.path.getmtime(
                        os.path.join(hb_dir, f"rank{r}")))
                except OSError:
                    pass
            healthy_for = max(0.0, max(beats, default=spawned_at)
                              - spawned_at)
        if hb_dir is not None:  # each incarnation gets a fresh dir
            shutil.rmtree(hb_dir, ignore_errors=True)
        failed_rank = failed[0]
        # Graceful preemption (EXIT_PREEMPTED): restart-worthy — the
        # checkpoint is durable and training should continue — but NEVER
        # attributed to the rank. A platform reclaiming capacity says
        # nothing about the slot's health, so the same-rank tracker that
        # drives elastic shrink is left untouched (acceptance: preemption
        # exits are never counted by the shrink tracker).
        preempted = (why == "failed"
                     and all(codes[r] == EXIT_PREEMPTED for r in failed))
        if preempted:
            why = "preempted (graceful, checkpoint drained)"
        elif len(failed) > 1:
            # group-wide failure (bad args, rendezvous breakage): never
            # evidence of one bad rank — don't let it drive a shrink
            last_failed, consecutive = None, 0
        else:
            consecutive = (consecutive + 1 if failed_rank == last_failed
                           else 1)
            last_failed = failed_rank
        if (not preempted and args.elastic_min_nproc > 0 and consecutive >= 2
                and nproc - 1 >= args.elastic_min_nproc):
            # the same single rank twice in a row: continue smaller. Not
            # charged against --max-restarts — shrinks are bounded by
            # nproc − min on their own.
            nproc -= 1
            last_failed, consecutive = None, 0
            print(f"[run] rank {failed_rank} {why} twice; resizing group "
                  f"to {nproc} (elastic)", file=sys.stderr)
            continue
        if restarts >= args.max_restarts:
            print(f"[run] rank {failed_rank} {why}; no restarts left",
                  file=sys.stderr)
            # a preemption with no restart budget left still exits with
            # the distinct code so outer schedulers can tell reclaimed
            # capacity from a genuine failure
            return EXIT_PREEMPTED if preempted else 1
        restarts += 1
        if (args.elastic_min_nproc > 0 and nproc < args.nproc_per_node
                and healthy_for >= args.elastic_regrow_after):
            # regrow probe: the shrunken group ran healthy long enough
            # that this failure reads as an independent transient, and the
            # boundary tears the group down anyway — readmit one worker
            # toward the original size. Fast failures never reach here
            # (uptime gate), so shrink evidence for a still-bad slot keeps
            # accumulating instead of being reset by probes; flapping is
            # bounded because probes only ride charged restarts.
            nproc += 1
            last_failed, consecutive = None, 0
            print(f"[run] regrowing group to {nproc} (elastic probe "
                  f"toward {args.nproc_per_node})", file=sys.stderr)
        print(f"[run] rank {failed_rank} {why}; restart "
              f"{restarts}/{args.max_restarts}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
