"""Graceful preemption (ISSUE 15): SIGTERM → drain → crash-atomic
emergency checkpoint → drain barrier → exit 0 → resume.

Tiers in this file:

- unit: the preempt request plane (signal-free ``request()``, the
  deterministic ``preempt.signal`` faultline site, ``bounded`` deadline
  aborts, the drain barrier's timeout fallback);
- launcher: ``run.py`` SIGTERM forwarding — children get ``--grace-s``
  to exit clean, stragglers are escalated to SIGKILL, and the report
  says which was which;
- ``chaos`` marker: the full ladder for BOTH engines — a 2-process
  training world preempts mid-epoch (deterministic fault site), every
  rank exits 0 with a checkpoint + journaled note, and a relaunch
  resumes with a continuous loss curve.
"""

import glob
import json
import math
import os
import signal
import subprocess
import sys
import time

import pytest

from horovod_tpu.core import faultline as flt
from horovod_tpu.core import preempt

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "preempt_worker.py")


@pytest.fixture(autouse=True)
def _clean_preempt_state():
    preempt.reset()
    flt.reset()
    yield
    preempt.reset()
    flt.reset()


# ---------------------------------------------------------------------------
# units: request plane
# ---------------------------------------------------------------------------


def test_request_and_reset():
    assert preempt.requested() is False
    preempt.request("test eviction")
    assert preempt.requested() is True
    assert preempt.reason() == "test eviction"
    preempt.reset()
    assert preempt.requested() is False


def test_faultline_site_delivers_deterministically():
    """preempt.signal:deliver:1@3 — the third poll 'receives SIGTERM';
    the request then LATCHES (one firing preempts the whole run)."""
    flt.configure("preempt.signal:deliver:1@3")
    assert preempt.requested() is False
    assert preempt.requested() is False
    assert preempt.requested() is True
    assert preempt.requested() is True  # latched
    assert "preempt.signal" in (preempt.reason() or "")


def test_bounded_deadline_aborts_wedged_rung():
    import threading

    release = threading.Event()
    t0 = time.monotonic()
    ok, _ = preempt.bounded(lambda: release.wait(30), 0.2, "wedged rung")
    assert ok is False
    assert time.monotonic() - t0 < 2.0
    release.set()
    ok, val = preempt.bounded(lambda: 42, 1.0, "fast rung")
    assert ok is True and val == 42


def test_drain_barrier_single_process_is_trivial(hvd):
    assert preempt.drain_barrier(0.1) is True


def test_drain_barrier_timeout_fallback(hvd, tmp_path, monkeypatch):
    """A peer that never reaches the barrier (dead, or never preempted)
    must not wedge the exit: the rendezvous times out and returns False
    — exit anyway."""
    from horovod_tpu.common import topology as topo

    monkeypatch.setenv("HVD_ELASTIC_DIR", str(tmp_path))
    monkeypatch.setattr(topo, "num_processes", lambda: 2)
    monkeypatch.setattr(topo, "process_index", lambda: 0)
    t0 = time.monotonic()
    assert preempt.drain_barrier(0.3) is False
    assert time.monotonic() - t0 < 3.0
    # Our own mark landed on the file plane for the (absent) peer.
    marks = os.listdir(tmp_path / "kv")
    assert any("preempt" in m and "p0" in m for m in marks), marks
    # With the peer's mark present, the same barrier passes.
    from horovod_tpu.core.elastic import FileKV

    FileKV(str(tmp_path / "kv")).set("hvd/preempt/g0/p1", "1.0")
    assert preempt.drain_barrier(2.0) is True


def test_journal_note_written(tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_PREEMPT_DIR", str(tmp_path))
    preempt.request("maintenance")
    path = preempt.journal_note(epoch=3, checkpoint="ckpt_3")
    assert path is not None
    rec = json.load(open(path))
    assert rec["kind"] == "preempted"
    assert rec["reason"] == "maintenance"
    assert rec["epoch"] == 3 and rec["checkpoint"] == "ckpt_3"


# ---------------------------------------------------------------------------
# launcher: SIGTERM forwarding + grace escalation
# ---------------------------------------------------------------------------


def _clean_env(extra=None):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra or {})
    return env


def _launch_and_sigterm(child_script, grace_s):
    """Start a 2-child world whose children print 'child up' once their
    own SIGTERM handling is in place, and SIGTERM the launcher only
    then: how long the launcher takes to import and spawn depends on the
    installation, so a fixed sleep either races it or wastes time."""
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.run", "-np", "2",
         "--grace-s", str(grace_s), "--",
         sys.executable, "-c", child_script],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=_clean_env(), cwd=_REPO)
    head = []
    while sum("child up" in ln for ln in head) < 2:
        line = proc.stdout.readline()
        if not line:
            break  # launcher died early: the asserts below will say how
        head.append(line)
    time.sleep(0.3)  # the launcher installs its handlers right after spawn
    proc.send_signal(signal.SIGTERM)
    out, err = proc.communicate(timeout=120)
    return proc.returncode, "".join(head) + out, err


def test_launcher_sigterm_forwards_and_reports_clean_drain():
    """Satellite: SIGTERM no longer tears the world down immediately —
    it is forwarded, children drain within --grace-s, the report names
    the clean exits, and a fully-clean drain exits 0."""
    child = ("import signal, sys, time\n"
             "def bye(s, f):\n"
             "    print('child drained clean', flush=True)\n"
             "    sys.exit(0)\n"
             "signal.signal(signal.SIGTERM, bye)\n"
             "print('child up', flush=True)\n"
             "time.sleep(120)\n")
    rc, out, err = _launch_and_sigterm(child, grace_s=20)
    assert rc == 0, (rc, err[-2000:])
    assert "SIGTERM received: forwarding to 2 child(ren)" in err, \
        err[-2000:]
    assert err.count("exited clean during the drain") == 2, err[-2000:]
    assert "2 clean, 0 escalated" in err, err[-2000:]


def test_launcher_sigterm_escalates_stragglers():
    """A child that ignores SIGTERM is SIGKILLed only after --grace-s,
    and the report says it was escalated."""
    child = ("import os, signal, sys, time\n"
             "if os.environ['HVD_PROCESS_ID'] == '1':\n"
             "    signal.signal(signal.SIGTERM, signal.SIG_IGN)\n"
             "else:\n"
             "    signal.signal(signal.SIGTERM,\n"
             "                  lambda s, f: sys.exit(0))\n"
             "print('child up', flush=True)\n"
             "time.sleep(120)\n")
    rc, out, err = _launch_and_sigterm(child, grace_s=2)
    assert rc == 128 + signal.SIGTERM, (rc, err[-2000:])
    assert "rank 1" in err and "escalating to SIGKILL" in err, err[-2000:]
    assert "1 clean, 1 escalated" in err, err[-2000:]


# ---------------------------------------------------------------------------
# chaos: the full ladder, both engines, with a resumed relaunch
# ---------------------------------------------------------------------------

ENGINES = ["native", "python"]


def _run_world(edir, engine, faults, epochs):
    cmd = [sys.executable, "-m", "horovod_tpu.run", "-np", "2", "--cpu",
           "--grace-s", "60"]
    for f in faults:
        cmd += ["--faults", f]
    cmd += ["--", sys.executable, _WORKER]
    env = _clean_env({
        "HVD_ENGINE": engine,
        "HVD_PREEMPT_TEST_DIR": edir,
        "HVD_PREEMPT_DIR": edir,
        "HVD_CHECKPOINT_DIR": os.path.join(edir, "ckpt"),
        "HVD_TEST_EPOCHS": str(epochs),
        "HVD_PREEMPT_BARRIER_S": "30",
        "HVD_FLIGHT_DIR": os.path.join(edir, "flight"),
    })
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=420, env=env, cwd=_REPO)


def _losses(edir, rank):
    path = os.path.join(edir, f"losses.rank{rank}.jsonl")
    with open(path) as fh:
        return [json.loads(ln) for ln in fh if ln.strip()]


@pytest.mark.chaos
@pytest.mark.parametrize("engine", ENGINES)
def test_chaos_preemption_drain_checkpoint_resume(engine, tmp_path):
    """ISSUE 15 acceptance, both engines: a deterministic 'SIGTERM'
    (the preempt.signal faultline site, armed identically on both
    ranks) lands mid-epoch-1. Every rank must drain the step, write the
    emergency checkpoint, journal a ``preempted`` note, and exit 0; the
    relaunch resumes from that checkpoint with a continuous loss curve
    (no restart-from-scratch jump)."""
    edir = str(tmp_path / f"preempt_{engine}")
    os.makedirs(edir)
    # The requested() poll runs once per batch; 16 batches/epoch at
    # these shapes, so @24 fires at epoch 1, batch ~7 on BOTH ranks.
    spec = "preempt.signal:deliver:1@24"
    proc = _run_world(edir, engine,
                      faults=[f"0:{spec}", f"1:{spec}"], epochs=6)
    out, err = proc.stdout, proc.stderr
    assert proc.returncode == 0, (proc.returncode, out[-4000:],
                                  err[-3000:])
    # Both ranks walked the ladder and exited 0.
    assert "PREEMPTED rank=0" in out and "PREEMPTED rank=1" in out, \
        out[-4000:]
    assert "ckpt=yes" in out, out[-4000:]
    assert "PREEMPT_TEST DONE" not in out  # evicted, not finished
    # Crash-atomic emergency checkpoint on disk.
    ckpts = glob.glob(os.path.join(edir, "ckpt", "checkpoint_*.msgpack"))
    assert ckpts, os.listdir(edir)
    # Journaled 'preempted' notes for both ranks, naming the injected
    # signal and the checkpoint.
    for rank in (0, 1):
        note = json.load(open(os.path.join(edir, "preempt",
                                           f"p{rank}.json")))
        assert note["kind"] == "preempted", note
        assert "preempt.signal" in note["reason"], note
        assert note["barrier_ok"] is True, note
    # The relaunch resumes from the emergency checkpoint and finishes.
    proc2 = _run_world(edir, engine, faults=[], epochs=6)
    out2 = proc2.stdout
    assert proc2.returncode == 0, (proc2.returncode, out2[-4000:],
                                   proc2.stderr[-3000:])
    assert "RESUMED rank=0 at epoch 2" in out2, out2[-3000:]
    assert out2.count("PREEMPT_TEST DONE") == 2, out2[-3000:]
    # Loss continuity across the eviction: epochs 0..1 from phase 1 +
    # 2..5 from phase 2, finite, no restart-from-scratch jump, net
    # progress end to end.
    recs = _losses(edir, 0)
    epochs_seen = [r["epoch"] for r in recs]
    assert epochs_seen == sorted(epochs_seen), recs
    # Epoch 1 was interrupted mid-epoch (its end-of-epoch record never
    # ran — that IS the eviction); the resume picks up at epoch 2 from
    # the emergency checkpoint's mid-epoch-1 state.
    assert {0, 2, 5} <= set(epochs_seen), epochs_seen
    assert 1 not in epochs_seen, epochs_seen
    losses = [r["loss"] for r in recs]
    assert all(math.isfinite(v) for v in losses), losses
    for prev, cur in zip(recs, recs[1:]):
        assert cur["loss"] <= prev["loss"] * 1.35 + 0.05, (prev, cur)
    assert losses[-1] < losses[0], losses
