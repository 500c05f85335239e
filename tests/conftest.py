"""Test harness: an 8-device virtual CPU mesh stands in for an 8-chip TPU
slice (the reference's equivalent trick is `mpirun -np N` on one host —
SURVEY.md §4). Two environment variables, set before jax is imported,
are all it takes: JAX_PLATFORMS=cpu and the XLA host-device-count flag.
"""

import os

# The performance sentinel (core/sentinel.py) defaults ON in production;
# in the suite, hundreds of heterogeneous jit programs share one process
# and every first-call compile would read as a dispatch anomaly — dumps
# and warnings all over the output. Tests that exercise the watchdog
# re-enable it explicitly (tests/test_sentinel.py resets the
# singleton). setdefault: an operator's explicit env still wins.
os.environ.setdefault("HVD_WATCHDOG", "0")

# The numerics observatory (core/numerics.py) likewise defaults ON
# (warn) in production; in the suite, hundreds of heterogeneous tiny
# models — several of which deliberately produce NaN — would trip
# verdicts/dump files (and the halt policy would abort legitimate
# tests). The numerics tests re-enable it explicitly per-test
# (tests/test_numerics.py sets HVD_NUMERICS and resets the module
# latches). setdefault: an operator's explicit env still wins, and
# spawned multiprocess worlds inherit it.
os.environ.setdefault("HVD_NUMERICS", "off")

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402

# Cmdline markers of multiprocess-world processes this suite spawns.
_WORLD_MARKERS = ("multiproc_worker.py", "launcher_worker.py",
                  "elastic_worker.py", "horovod_tpu.run")


def _ancestor_pids() -> set:
    pids = set()
    pid = os.getpid()
    for _ in range(64):  # bounded walk; /proc chains are short
        pids.add(pid)
        try:
            with open(f"/proc/{pid}/status") as fh:
                ppid = next((int(line.split()[1]) for line in fh
                             if line.startswith("PPid:")), 0)
        except (OSError, ValueError):
            break
        if ppid <= 1:
            break
        pid = ppid
    return pids


def _stale_world_processes():
    """Leftover multiprocess-world processes from a previous (crashed or
    still-running) session. The host has ONE CPU core: a stale 2-process
    world silently starves every new 8-device rendezvous until XLA:CPU's
    40 s abort — the documented failure mode (CLAUDE.md). Detect by
    cmdline marker or by HVD_COORDINATOR_ADDRESS in the environment (the
    latter catches orphaned inner pytest workers whose launcher died)."""
    mine = _ancestor_pids()
    stale = []
    try:
        entries = os.listdir("/proc")
    except OSError:
        return stale
    for entry in entries:
        if not entry.isdigit() or int(entry) in mine:
            continue
        try:
            with open(f"/proc/{entry}/cmdline", "rb") as fh:
                cmd = fh.read().replace(b"\0", b" ").decode(
                    "utf-8", "replace").strip()
        except OSError:
            continue  # gone, or not ours to inspect
        hit = any(m in cmd for m in _WORLD_MARKERS)
        if not hit:
            try:
                with open(f"/proc/{entry}/environ", "rb") as fh:
                    hit = b"HVD_COORDINATOR_ADDRESS=" in fh.read()
            except OSError:
                hit = False
        if hit:
            stale.append((int(entry), cmd[:120]))
    return stale


def pytest_configure(config):
    # Declared markers: `slow` gates the opt-in multi-minute tier
    # (ROADMAP tier-1 runs -m 'not slow'); `chaos` tags the elastic
    # failure-injection scenarios (tests/test_world_elastic.py) — they
    # run in tier-1 like the other multiprocess worlds (sequentially;
    # the stale-world preflight below already covers their children).
    config.addinivalue_line(
        "markers", "slow: opt-in multi-minute tier (HVD_SLOW_TESTS=1)")
    config.addinivalue_line(
        "markers", "chaos: elastic chaos-monkey multiprocess scenarios")
    if os.environ.get("HVD_COORDINATOR_ADDRESS") or os.environ.get(
            "HVD_NUM_PROCESSES") or os.environ.get("HVD_PREFLIGHT_SKIP"):
        # We ARE a spawned world member (frontend suites re-run under the
        # launcher) — sibling ranks and the launcher are expected, not
        # stale. HVD_PREFLIGHT_SKIP is the manual override.
        return
    stale = _stale_world_processes()
    if stale:
        listing = "\n".join(f"  pid {pid}: {cmd}" for pid, cmd in stale)
        raise pytest.UsageError(
            "stale multiprocess-world processes are still running from an "
            "earlier session; on this one-core host they would starve "
            "every 8-device rendezvous into 40 s XLA aborts instead of a "
            "clear failure. Kill them (or set HVD_PREFLIGHT_SKIP=1 if "
            f"they are intentional):\n{listing}")


@pytest.fixture(scope="session")
def hvd():
    import horovod_tpu as hvd

    hvd.init()
    return hvd
