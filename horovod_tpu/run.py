"""Local multi-process launcher — the role ``mpirun`` plays for the
reference (reference: docs/running.md tells users to invoke
``mpirun -np N python train.py``; there is no launcher in-tree at v0.15.2).

    python -m horovod_tpu.run -np 2 python train.py --epochs 1

Spawns N controller processes wired together through ``jax.distributed``
(coordinator on a free localhost port). On a CPU host each process gets
``--ncpus-per-proc`` virtual chips so an N-process × M-chip world can be
simulated exactly like the reference's single-host ``mpirun -np N`` test
tier (SURVEY.md §4). On real multi-host TPU pods, prefer one process per
host started by your scheduler; this launcher is for local runs and tests.

Failure semantics:

- Default (``mpirun`` parity): the first child death is REPORTED — which
  rank, which pid, which signal or exit code — before the remaining
  children are torn down, and that child's status becomes the
  launcher's own (``128+signum`` for signal deaths).
- ``--elastic`` (supervisor mode, core/elastic.py): children run with
  ``HVD_ELASTIC=1`` and are *supervised*, not collectively killed. A
  crashed/killed child gets a death note; survivors keep training on a
  shrunk world; after an ``HVD_ELASTIC_BLACKLIST_S`` backoff (doubled
  per repeat death, capped by ``--max-restarts``) the supervisor files a
  rejoin request, survivors checkpoint and exit with the restart code,
  and the whole world is relaunched at the next generation — resuming
  from the newest checkpoint with the recovered rank readmitted.
  ``--min-np`` bounds how far the world may shrink in place.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import signal
import socket
import subprocess
import sys
import threading
import time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _stream(prefix: str, pipe, out):
    for line in iter(pipe.readline, ""):
        out.write(f"{prefix}{line}")
        out.flush()
    pipe.close()


def _describe_exit(rank: int, pid: int, code: int) -> str:
    """Human attribution of one child's exit (the satellite the old
    launcher lacked: *which* rank died, *how*, before the teardown)."""
    if code < 0:
        try:
            name = signal.Signals(-code).name
        except ValueError:
            name = f"signal {-code}"
        return f"rank {rank} (pid {pid}) was killed by {name}"
    return f"rank {rank} (pid {pid}) exited with code {code}"


def _exit_status(code: int) -> int:
    """Shell-convention launcher status for a child status: 128+signum
    for signal deaths (a raw negative returncode would be truncated to a
    meaningless byte), the child's own code otherwise."""
    return 128 - code if code < 0 else code


# Keep in sync with horovod_tpu.core.elastic.RESTART_EXIT_CODE (pinned by
# tests/test_world_elastic.py). The launcher imports nothing that could
# initialise a jax backend: jax itself is already imported (the package
# __init__ pulls it in), which is harmless, but a parent that holds the
# chip starves every child it spawns.
RESTART_EXIT_CODE = 77


# Chip grids of the multi-chip TPU hosts this launcher can divide, by
# chip count, for hosts that do not say it themselves in
# TPU_CHIPS_PER_HOST_BOUNDS (v5e/v4 hosts carry 2x2 or 2x4 chips).
_TPU_HOST_SHAPES = {4: (2, 2, 1), 8: (2, 4, 1)}

# libtpu's whole-host spellings of what the per-process variables below
# say per child; a child that inherited both would be told two things.
_TPU_HOST_WIDE_VARS = ("TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS",
                       "TPU_WORKER_HOSTNAMES", "TPU_WORKER_ID")


def _host_tpu_chips() -> int:
    """TPU chips this host exposes, counted from their device nodes (vfio
    groups on v5e and later, accel nodes before) — never through jax:
    asking jax would initialise the backend, and a launcher that holds
    the chips starves its children."""
    import glob

    return (len(glob.glob("/dev/vfio/[0-9]*"))
            or len(glob.glob("/dev/accel[0-9]*")))


def _tpu_child_envs(num_proc: int, chips: int, environ) -> list:
    """One environment update per child that gives each controller on
    this host ONE of the host's ``chips`` TPU chips, through libtpu's
    per-process visibility variables (ranks are chips: the ``mpirun -np
    N`` shape). Without them every child initialises libtpu for all the
    chips and all but the first die on its lockfile. Empty updates when
    there is nothing to divide (no chips, or one process that takes the
    whole host); ``SystemExit`` naming the cause otherwise.

    Blocks of several chips per process are refused, not guessed: libtpu
    accepts a block only if its chips are neighbours in the chip grid
    ("Chip 0x0x0 not on Host 0x1x0" otherwise), and the device-node
    numbers the launcher can see neither follow the grid nor stay the
    same across machines (two 2x2 v5e hosts: nodes 0-3 at (1,0), (1,1),
    (0,1), (0,0) on one, at (1,1), (0,1), (0,0), (1,0) on the other)."""
    if chips == 0 or num_proc == 1:
        return [{} for _ in range(num_proc)]
    grid = None
    if environ.get("TPU_CHIPS_PER_HOST_BOUNDS"):
        grid = tuple(int(v) for v in
                     environ["TPU_CHIPS_PER_HOST_BOUNDS"].split(","))
    if grid is None or math.prod(grid) != chips:
        grid = _TPU_HOST_SHAPES.get(chips)
    if num_proc != chips or grid is None:
        raise SystemExit(
            f"horovod_tpu.run: -np {num_proc} cannot be placed on this "
            f"host's {chips} TPU chip(s). The launcher gives each process "
            f"exactly one chip (-np {chips}"
            + ("" if grid else ", with TPU_CHIPS_PER_HOST_BOUNDS set: "
               "this host's chip grid is not known")
            + ") or runs one controller over the whole host (-np 1); "
            "--cpu simulates any world. libtpu does not take the "
            "several-chip blocks a launcher can name (docs/tpus.md).")
    ports = [_free_port() for _ in range(num_proc)]
    return [{
        "TPU_VISIBLE_CHIPS": str(i),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": ",".join(map(str, grid)),
        "TPU_PROCESS_ADDRESSES": ",".join(f"localhost:{p}" for p in ports),
        "TPU_PROCESS_PORT": str(ports[i]),
        "CLOUD_TPU_TASK_ID": str(i),
    } for i in range(num_proc)]


def _graceful_stop(procs, grace_s: float, signum: int) -> int:
    """Graceful preemption drain (the launcher half of the ladder in
    core/preempt.py): forward SIGTERM to every live child, wait up to
    ``grace_s`` for them to drain/checkpoint/exit on their own, and
    escalate to SIGKILL only for the stragglers — reporting which
    children exited clean vs were escalated. Returns the launcher
    status: 0 when every child exited 0 (a fully clean eviction),
    128+signum otherwise."""
    alive = [i for i, p in enumerate(procs) if p.poll() is None]
    sys.stderr.write(
        f"[launcher] {signal.Signals(signum).name} received: forwarding "
        f"to {len(alive)} child(ren) and draining up to "
        f"{grace_s:.0f}s before escalating\n")
    for i in alive:
        try:
            procs[i].terminate()  # SIGTERM: the child's graceful ladder
        except OSError:
            pass
    deadline = time.monotonic() + max(0.0, grace_s)
    reported: set = set()
    while time.monotonic() < deadline:
        for i, p in enumerate(procs):
            if i in reported or p.poll() is None:
                continue
            reported.add(i)
            if p.returncode == 0:
                sys.stderr.write(f"[launcher] rank {i} (pid {p.pid}) "
                                 "exited clean during the drain\n")
            else:
                sys.stderr.write(
                    "[launcher] "
                    + _describe_exit(i, p.pid, p.returncode)
                    + " during the drain\n")
        if all(p.poll() is not None for p in procs):
            break
        time.sleep(0.05)
    escalated = [i for i, p in enumerate(procs) if p.poll() is None]
    for i in escalated:
        sys.stderr.write(
            f"[launcher] rank {i} (pid {procs[i].pid}) did not exit "
            f"within --grace-s={grace_s:.0f}; escalating to SIGKILL\n")
        try:
            procs[i].kill()
        except OSError:
            pass
    for p in procs:
        try:
            p.wait(timeout=10)
        except Exception:
            pass
    clean = all(p.returncode == 0 for p in procs)
    sys.stderr.write(
        f"[launcher] drain complete: "
        f"{sum(1 for p in procs if p.returncode == 0)} clean, "
        f"{len(escalated)} escalated\n")
    return 0 if clean else 128 + signum


def _run_failfast(args, spawn_world) -> int:
    """mpirun parity: first child death tears the world down — after an
    attributed report of who died and how. A sequential wait() would
    never observe a higher-index child dying while process 0 blocks in a
    collective, hence the poll loop. SIGTERM (the platform's eviction
    signal) is NOT a teardown: it is forwarded and the children get
    ``--grace-s`` to drain before the SIGKILL escalation."""
    procs, threads = spawn_world({})

    def _kill_all(signum=None, frame=None):
        # Casualty/interactive teardown: SIGTERM first, but children now
        # TRAP it for the graceful-preemption ladder — a survivor blocked
        # inside a cross-rank collective never reaches the batch-boundary
        # poll, so escalate to SIGKILL after a SHORT window. This is a
        # crash teardown, not an eviction: nobody gets --grace-s here
        # (mpirun parity — quick, bounded, never wedged).
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            if all(p.poll() is not None for p in procs):
                return
            time.sleep(0.05)
        for p in procs:
            if p.poll() is None:
                try:
                    p.kill()
                except OSError:
                    pass

    sigterm = []

    def _on_term(signum, frame):
        sigterm.append(signum)  # handled by the poll loop, not inline

    signal.signal(signal.SIGINT, _kill_all)
    signal.signal(signal.SIGTERM, _on_term)

    rc = 0
    pending = set(range(len(procs)))
    while pending:
        if sigterm:
            rc = _graceful_stop(procs, args.grace_s, sigterm[0])
            pending.clear()
            break
        exited = [i for i in pending if procs[i].poll() is not None]
        for i in exited:
            pending.discard(i)
            code = procs[i].returncode
            if code != 0 and rc == 0:
                # The FIRST failure is the cause; children _kill_all
                # subsequently terminates (SIGTERM, code -15) are
                # casualties, not causes — the cause's status is the
                # launcher's status (128+signum for a signal death; the
                # old launcher returned the raw negative, which the
                # shell mangled into its own meaningless byte).
                rc = _exit_status(code)
                sys.stderr.write(
                    "[launcher] " + _describe_exit(i, procs[i].pid, code)
                    + "; terminating the remaining processes\n")
                _kill_all()
        if pending and not exited:
            time.sleep(0.05)
    for t in threads:
        t.join(timeout=5)
    return rc


def _parse_faults(entries) -> dict:
    """``--faults RANK:SPEC`` (repeatable) -> {rank: spec}. Several
    entries for one rank join with commas (the HVD_FAULTS grammar).
    Specs are validated HERE, before any child spawns: a typo'd site or
    mode must fail the launch, not crash-loop every relaunched
    generation through an import-time FaultSpecError in the child.
    (core.faultline touches no jax API, so the launcher still
    initialises no backend.)"""
    from horovod_tpu.core import faultline as _faultline

    out: dict = {}
    for entry in entries or ():
        rank_s, sep, spec = entry.partition(":")
        try:
            rank = int(rank_s)
        except ValueError:
            rank = -1
        if not sep or rank < 0 or not spec:
            raise SystemExit(
                f"--faults {entry!r}: want RANK:SPEC (e.g. "
                "1:hb.beat:skip:*)")
        try:
            _faultline._parse(spec)
        except _faultline.FaultSpecError as exc:
            raise SystemExit(f"--faults {entry!r}: {exc}") from None
        out[rank] = (out[rank] + "," + spec) if rank in out else spec
    return out


def _prune_elastic_dir(edir: str, generation: int):
    """Supervisor hygiene: consumed control files from generation N-2
    and older are dropped at relaunch — death notes, rejoin requests,
    restart votes and the fallback-KV namespace otherwise accumulate
    forever across a long-lived elastic job. Checkpoints and the epoch
    journal are never touched (they ARE the resume state)."""
    floor = generation - 1  # keep the previous generation for forensics

    def gen_of(path):
        try:
            with open(path) as fh:
                return int(json.load(fh).get("generation", -1))
        except (OSError, ValueError, TypeError):
            return None

    # (rejoin requests need no generation filter here: the supervisor
    # loop already consumes the WHOLE rejoin dir right after this prune,
    # every relaunch.)
    d = os.path.join(edir, "death")
    if os.path.isdir(d):
        for name in os.listdir(d):
            path = os.path.join(d, name)
            g = gen_of(path)
            if g is not None and g < floor:
                try:
                    os.unlink(path)
                except OSError:
                    pass
    kv = os.path.join(edir, "kv")
    if os.path.isdir(kv):
        # Fallback-plane keys are namespaced hvd~elastic~g<gen>[~...]
        # (core/elastic.py FileKV): prune whole dead generations.
        for name in os.listdir(kv):
            if not name.startswith("hvd~elastic~g"):
                continue
            head = name[len("hvd~elastic~g"):].split("~", 1)[0]
            head = head.split(".", 1)[0]  # tmp suffixes
            try:
                g = int(head)
            except ValueError:
                continue
            if g < floor:
                try:
                    os.unlink(os.path.join(kv, name))
                except OSError:
                    pass


def _supervise_elastic(args, spawn_world) -> int:
    """Elastic supervisor (core/elastic.py): children survive peer
    death; this loop supplies the process-management half — death notes,
    blacklist-then-readmit rejoin requests, and capped full-world
    relaunches when the members vote for a coordinated restart."""
    import tempfile

    edir = args.elastic_dir or os.environ.get("HVD_ELASTIC_DIR") \
        or tempfile.mkdtemp(prefix="hvd_elastic_")
    os.makedirs(edir, exist_ok=True)
    sys.stderr.write(f"[launcher] elastic supervisor: dir {edir}, "
                     f"min-np {args.min_np}, "
                     f"max-restarts {args.max_restarts}\n")
    restarts = {i: 0 for i in range(args.num_proc)}
    faults_by_rank = getattr(args, "_faults_by_rank", {}) or {}
    world_relaunches = 0
    generation = 0
    interrupted = []

    def _on_signal(signum, frame):
        interrupted.append(signum)

    signal.signal(signal.SIGINT, _on_signal)
    signal.signal(signal.SIGTERM, _on_signal)

    # Read the knob from env directly — core.elastic brings up the jax
    # distributed client, and the supervisor must never initialise a
    # backend (the same reason RESTART_EXIT_CODE is duplicated above).
    # Keep the default in sync with core/elastic.py blacklist_s().
    try:
        blacklist = float(os.environ.get("HVD_ELASTIC_BLACKLIST_S", "5"))
    except ValueError:
        blacklist = 5.0

    while True:
        # Hygiene: control files (death notes, rejoin requests, restart
        # votes, fallback-KV keys) from generation N-2 and older are
        # consumed — prune them so HVD_ELASTIC_DIR stays bounded across
        # a long-lived job's relaunches.
        _prune_elastic_dir(edir, generation)
        # Consume control files from the previous generation: a stale
        # rejoin request would bounce the fresh world straight back into
        # a restart loop.
        for name in ("restart.json",):
            try:
                os.unlink(os.path.join(edir, name))
            except OSError:
                pass
        rejoin_dir = os.path.join(edir, "rejoin")
        if os.path.isdir(rejoin_dir):
            for f in os.listdir(rejoin_dir):
                try:
                    os.unlink(os.path.join(rejoin_dir, f))
                except OSError:
                    pass

        procs, threads = spawn_world({
            "HVD_ELASTIC": "1",
            "HVD_ELASTIC_DIR": edir,
            "HVD_ELASTIC_GENERATION": str(generation),
            "HVD_ELASTIC_MIN_NP": str(args.min_np),
        })
        statuses: dict = {}
        rejoin_due: dict = {}
        while len(statuses) < len(procs) and not interrupted:
            for i, p in enumerate(procs):
                if i in statuses or p.poll() is None:
                    continue
                code = p.returncode
                statuses[i] = code
                desc = _describe_exit(i, p.pid, code)
                if code == RESTART_EXIT_CODE:
                    sys.stderr.write(f"[launcher] {desc} "
                                     "(coordinated-restart vote)\n")
                elif code == 0:
                    sys.stderr.write(f"[launcher] rank {i} (pid {p.pid}) "
                                     "completed\n")
                else:
                    # Injections are armed in generation 0 only: a
                    # gen>0 crash is organic and must never be reported
                    # as injected (the misattribution this PR exists to
                    # prevent).
                    injected = (faults_by_rank.get(i)
                                if generation == 0 else None)
                    if injected:
                        # The death report must say the child ran with
                        # ARMED injections: a chaos casualty must never
                        # read as an organic incident in a post-mortem.
                        desc += (f" (this rank had active fault "
                                 f"injections: {injected})")
                    sys.stderr.write(
                        f"[launcher] {desc}; elastic world continues "
                        "degraded\n")
                    try:
                        os.makedirs(os.path.join(edir, "death"),
                                    exist_ok=True)
                        note = {"process": i, "pid": p.pid,
                                "status": code,
                                "generation": generation,
                                "wall": round(time.time(), 3)}
                        if injected:
                            note["faults"] = injected
                        with open(os.path.join(
                                edir, "death",
                                f"p{i}.supervisor.json"), "w") as fh:
                            json.dump(note, fh)
                    except OSError:
                        pass
                    if restarts[i] < args.max_restarts:
                        backoff = blacklist * (2 ** restarts[i])
                        restarts[i] += 1
                        rejoin_due[i] = time.monotonic() + backoff
                        sys.stderr.write(
                            f"[launcher] rank {i} blacklisted for "
                            f"{backoff:.1f}s before readmission "
                            f"(restart {restarts[i]}/"
                            f"{args.max_restarts})\n")
                    else:
                        sys.stderr.write(
                            f"[launcher] rank {i} exceeded "
                            f"--max-restarts={args.max_restarts}; "
                            "not readmitting\n")
            # A rank can be lease-verdicted by its peers while its
            # process is WEDGED rather than dead (blocked inside the
            # runtime): the survivors' death notes name it — reap it,
            # or the wait loop above blocks on it forever.
            death_dir = os.path.join(edir, "death")
            if os.path.isdir(death_dir):
                for i, p in enumerate(procs):
                    if i in statuses or p.poll() is not None:
                        continue
                    note = os.path.join(death_dir, f"p{i}.json")
                    try:
                        with open(note) as fh:
                            rec = json.load(fh)
                    except (OSError, ValueError):
                        continue
                    if rec.get("generation") == generation:
                        injected = (faults_by_rank.get(i)
                                    if generation == 0 else None)
                        extra = (f" (this rank had active fault "
                                 f"injections: {injected})"
                                 if injected else "")
                        sys.stderr.write(
                            f"[launcher] rank {i} (pid {p.pid}) was "
                            "declared dead by its peers but is still "
                            f"running (wedged); killing it{extra}\n")
                        p.kill()
            now = time.monotonic()
            for i in [i for i, due in rejoin_due.items() if now >= due]:
                del rejoin_due[i]
                try:
                    os.makedirs(rejoin_dir, exist_ok=True)
                    with open(os.path.join(rejoin_dir, f"p{i}.json"),
                              "w") as fh:
                        json.dump({"process": i, "generation": generation,
                                   "wall": round(time.time(), 3)}, fh)
                    sys.stderr.write(
                        f"[launcher] rank {i} blacklist expired; rejoin "
                        "request filed (survivors restart at their next "
                        "epoch boundary)\n")
                except OSError as exc:
                    sys.stderr.write(
                        f"[launcher] cannot file rejoin request: {exc}\n")
            time.sleep(0.05)
        if interrupted:
            if signal.SIGTERM in interrupted:
                # Platform eviction: forward, grace-drain, escalate —
                # same ladder as the non-elastic launcher.
                return _graceful_stop(procs, args.grace_s,
                                      signal.SIGTERM)
            # SIGINT (interactive): quick teardown — children trap
            # SIGTERM (preempt intake), so a short SIGKILL escalation
            # keeps "quick" true instead of leaving drain-laddering
            # orphans behind the returned prompt.
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            deadline = time.monotonic() + 5.0
            while (time.monotonic() < deadline
                   and any(p.poll() is None for p in procs)):
                time.sleep(0.05)
            for p in procs:
                if p.poll() is None:
                    try:
                        p.kill()
                    except OSError:
                        pass
            return 130
        for t in threads:
            t.join(timeout=5)

        votes = sorted(i for i, c in statuses.items()
                       if c == RESTART_EXIT_CODE)
        completed = sorted(i for i, c in statuses.items() if c == 0)
        crashed = sorted(i for i, c in statuses.items()
                         if c not in (0, RESTART_EXIT_CODE))
        if completed and not votes:
            # The job finished (possibly degraded — a crashed rank that
            # was never readmitted is reported above, not fatal).
            return 0
        if (votes or crashed) and world_relaunches < args.max_restarts:
            world_relaunches += 1
            generation += 1
            sys.stderr.write(
                f"[launcher] relaunching the world: generation "
                f"{generation} (votes {votes}, crashed {crashed}, "
                f"relaunch {world_relaunches}/{args.max_restarts})\n")
            continue
        if crashed:
            code = statuses[crashed[0]]
            sys.stderr.write(
                "[launcher] giving up: relaunch budget exhausted\n")
            return _exit_status(code)
        if votes:
            # Members exited mid-training expecting a relaunch the
            # budget no longer allows — that is an incomplete job, not
            # a success.
            sys.stderr.write(
                "[launcher] giving up: relaunch budget exhausted with "
                f"pending restart votes from ranks {votes}\n")
            return 1
        return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m horovod_tpu.run",
        description="Launch N local horovod_tpu controller processes.")
    ap.add_argument("-np", "--num-proc", type=int, required=True)
    ap.add_argument("--ncpus-per-proc", type=int, default=4,
                    help="virtual CPU chips per process (CPU simulation)")
    ap.add_argument("--cpu", action="store_true", default=False,
                    help="force the CPU platform (default: inherit)")
    ap.add_argument("--tag-output", action="store_true", default=True)
    ap.add_argument("--timeline", metavar="DIR", default=None,
                    help="distributed tracing: every process writes "
                         "timeline.rank{N}.json into DIR (sets "
                         "HVD_TIMELINE), and the launcher merges them "
                         "into one Perfetto trace at exit")
    ap.add_argument("--telemetry-port-base", type=int, metavar="PORT",
                    default=None,
                    help="live telemetry: process i serves /metrics and "
                         "/healthz on 127.0.0.1:PORT+i (sets "
                         "HVD_TELEMETRY_PORT; query with "
                         "python -m horovod_tpu.utils.stats "
                         "http://127.0.0.1:PORT)")
    ap.add_argument("--elastic", action="store_true", default=False,
                    help="supervisor mode: children run with "
                         "HVD_ELASTIC=1, a dead rank does not kill the "
                         "world, and recovered ranks rejoin at an epoch "
                         "boundary through a full-world relaunch "
                         "(docs/running.md 'Elastic worlds')")
    ap.add_argument("--min-np", type=int, default=1, metavar="K",
                    help="elastic: smallest process count the world may "
                         "shrink to in place; below it survivors wait "
                         "for a relaunch (default 1)")
    ap.add_argument("--max-restarts", type=int, default=3, metavar="N",
                    help="elastic: per-rank readmissions and full-world "
                         "relaunches allowed before giving up "
                         "(default 3)")
    ap.add_argument("--grace-s", type=float, default=30.0, metavar="S",
                    help="graceful preemption: on SIGTERM, forward the "
                         "signal to every child and wait S seconds for "
                         "them to drain/checkpoint/exit 0 before "
                         "escalating to SIGKILL (default 30; both "
                         "elastic and plain modes)")
    ap.add_argument("--faults", action="append", metavar="RANK:SPEC",
                    default=None,
                    help="fault injection (core/faultline.py): arm "
                         "HVD_FAULTS=SPEC in rank RANK's child only "
                         "(repeatable; e.g. --faults "
                         "'1:hb.beat:skip:*' freezes rank 1's "
                         "heartbeat). Scoped to generation 0 — "
                         "relaunched generations run clean. The "
                         "supervisor's death report names a dead "
                         "child's active injections")
    ap.add_argument("--elastic-dir", default=None, metavar="DIR",
                    help="elastic: state directory shared with the "
                         "children (epoch journal, death notes, rejoin "
                         "requests, checkpoints; default "
                         "HVD_ELASTIC_DIR or a fresh temp dir)")
    ap.add_argument("command", nargs=argparse.REMAINDER,
                    help="command to run, e.g. python train.py --epochs 1")
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no command given")
    cmd = args.command
    if cmd[0] == "--":
        cmd = cmd[1:]
    args._faults_by_rank = _parse_faults(args.faults)
    for r in args._faults_by_rank:
        if r >= args.num_proc:
            ap.error(f"--faults rank {r} outside the -np "
                     f"{args.num_proc} world")

    # Distributed tracing: --timeline DIR (or an inherited HVD_TIMELINE)
    # rides into every child; children resolve their own per-rank file
    # from HVD_PROCESS_ID (core/timeline.py), the launcher auto-merges.
    timeline = args.timeline or os.environ.get("HVD_TIMELINE") \
        or os.environ.get("HOROVOD_TIMELINE")
    timeline_dir = None
    if timeline:
        from horovod_tpu.core.timeline import is_dir_mode

        if is_dir_mode(timeline):
            os.makedirs(timeline, exist_ok=True)
            timeline_dir = timeline
            # A reused dir must not leak a previous run's ranks into the
            # merge: a -np 2 rerun over an old -np 4 capture would
            # attribute waits to ranks that were never in this world.
            import glob as _glob

            for stale in _glob.glob(
                    os.path.join(timeline, "timeline.rank*.json")) + \
                    _glob.glob(os.path.join(timeline,
                                            "timeline.merged.json")):
                try:
                    os.unlink(stale)
                except OSError:
                    pass
        elif args.num_proc > 1:
            # N children opening ONE .json would clobber each other into
            # an interleaved, unloadable trace — and there would be
            # nothing to merge. Refuse loudly instead of corrupting.
            ap.error(
                f"--timeline/HVD_TIMELINE={timeline} is a single file; "
                f"{args.num_proc} processes need a directory "
                "(per-rank traces + auto-merge)")

    # One block of this host's TPU chips per child, unless the world is
    # on the CPU. Decided once, before anything is spawned, so a layout
    # that cannot work is refused at once instead of dying in libtpu.
    on_cpu = args.cpu or os.environ.get("JAX_PLATFORMS") == "cpu"
    tpu_envs = _tpu_child_envs(
        args.num_proc, 0 if on_cpu else _host_tpu_chips(), os.environ)

    def _spawn_world(extra_env: dict):
        port = _free_port()
        procs, threads = [], []
        for i in range(args.num_proc):
            env = dict(os.environ)
            if tpu_envs[i]:
                for name in _TPU_HOST_WIDE_VARS:
                    env.pop(name, None)
                env.update(tpu_envs[i])
            env["HVD_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
            env["HVD_NUM_PROCESSES"] = str(args.num_proc)
            env["HVD_PROCESS_ID"] = str(i)
            if (i in args._faults_by_rank
                    and extra_env.get("HVD_ELASTIC_GENERATION",
                                      "0") == "0"):
                # Per-rank fault scope: the spec reaches ONE child, and
                # only the FIRST world — a relaunched generation exists
                # to prove a clean resume, and re-arming the same fault
                # there would crash-loop it through the whole restart
                # budget.
                env["HVD_FAULTS"] = args._faults_by_rank[i]
            env.update(extra_env)
            if timeline:
                env["HVD_TIMELINE"] = timeline
            if args.telemetry_port_base is not None:
                env["HVD_TELEMETRY_PORT"] = str(
                    args.telemetry_port_base + i)
            if args.cpu:
                env["JAX_PLATFORMS"] = "cpu"
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "") +
                    f" --xla_force_host_platform_device_count="
                    f"{args.ncpus_per_proc}").strip()
            p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                 stderr=subprocess.STDOUT, text=True)
            procs.append(p)
            prefix = f"[{i}] " if args.tag_output else ""
            t = threading.Thread(target=_stream,
                                 args=(prefix, p.stdout, sys.stdout),
                                 daemon=True)
            t.start()
            threads.append(t)
        return procs, threads

    if args.elastic:
        rc = _supervise_elastic(args, _spawn_world)
    else:
        rc = _run_failfast(args, _spawn_world)
    if timeline_dir:
        # Collect + auto-merge the per-rank traces (whatever landed on
        # disk — the truncation-tolerant reader handles ranks that died
        # mid-write). Best-effort: a merge failure must not change the
        # job's exit code.
        try:
            from horovod_tpu.utils import trace as trace_mod

            info = trace_mod.merge(timeline_dir)
            sys.stderr.write(
                f"[launcher] merged timeline: {info['files']} rank "
                f"file(s), {info['events']} events -> {info['path']}\n")
        except Exception as exc:
            sys.stderr.write(f"[launcher] timeline merge failed: {exc}\n")
    return rc


if __name__ == "__main__":
    sys.exit(main())
