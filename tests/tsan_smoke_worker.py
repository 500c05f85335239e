"""TSan smoke worker: drive the native engine hard under ThreadSanitizer.

Spawned by tests/test_analysis.py (opt-in HVD_SLOW_TESTS tier) with
``LD_PRELOAD=<libtsan>`` and ``HVD_SANITIZE=thread`` so load_library
picks the instrumented ``libhvdcore.tsan.<key>.so``. The executor is pure
numpy — no jax backend initialization, no devices — which keeps the run
about the ENGINE's concurrency: multi-threaded submits, fusion batches,
donated buffers, waiter wakeups, stats reads, and shutdown-drain, all
racing the C++ loop/watchdog threads. Any "WARNING: ThreadSanitizer"
line in our output fails the smoke.
"""

import os
import sys
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


class LocalExecutor:
    """Identity 'collective' data plane (world of one, no jax)."""

    measure_staging = False
    last_stage_s = 0.0
    pool = None
    wire_policy = "none"
    last_wire_bytes = 0
    last_wire_compressed = 0

    def allreduce(self, flat, average):
        self.last_wire_bytes = flat.nbytes
        return flat * 1.0

    def allgather(self, t):
        self.last_wire_bytes = t.nbytes
        return np.concatenate([t, t])

    def broadcast(self, t, root_rank):
        self.last_wire_bytes = t.nbytes
        return t * 1.0


def submitter(engine, tid, steps, errors):
    try:
        for i in range(steps):
            handles = [
                engine.allreduce_async(f"t{tid}.g{i}.{j}",
                                       np.full(513, float(j), np.float32),
                                       average=True)
                for j in range(4)
            ]
            donated = np.arange(256, dtype=np.float32)
            handles.append(engine.allreduce_async(
                f"t{tid}.d{i}", donated, average=False, donate=True))
            handles.append(engine.allgather_async(
                f"t{tid}.ag{i}", np.arange(16, dtype=np.int32)))
            handles.append(engine.broadcast_async(
                f"t{tid}.bc{i}", np.zeros(64, np.float32), 0))
            for h in handles:
                engine.synchronize(h)
    except Exception as exc:  # pragma: no cover - failure path
        errors.append(f"thread {tid}: {exc!r}")


def ring_hammer(engine, tid, steps, errors):
    """Batched-submit producer: CAS-publish into the MPSC submit ring
    from several threads at once, against a ring sized small enough that
    the ring-full locked fallback also gets exercised."""
    from horovod_tpu.core import engine as eng

    try:
        for i in range(steps):
            reqs = [eng.SubmitRequest(f"r{tid}.b{i}.{j}",
                                      np.full(97, float(j + 1), np.float32),
                                      average=False)
                    for j in range(6)]
            handles = engine.submit_n("allreduce", reqs)
            for h in handles:
                engine.synchronize(h)
    except Exception as exc:  # pragma: no cover - failure path
        errors.append(f"ring thread {tid}: {exc!r}")


def main():
    from horovod_tpu.core.native_engine import NativeEngine

    engine = NativeEngine(executor=LocalExecutor(), cycle_time_s=0.002,
                          stall_warning_s=0.0)
    errors: list = []
    threads = [threading.Thread(target=submitter,
                                args=(engine, t, 25, errors))
               for t in range(3)]
    for t in threads:
        t.start()
    # Concurrent readers: stats + params churn while submits fly.
    for _ in range(50):
        engine.current_params()
        engine.set_params(cycle_time_s=0.002)
    for t in threads:
        t.join()
    engine.shutdown()

    # Phase 2: the lock-free submit ring under multi-producer pressure.
    # An 8-slot ring with 4 producers × 6-request batches guarantees both
    # the CAS publish path and the ring-full locked fallback run, racing
    # the loop thread's fold-on-mu_-entry consumer.
    os.environ["HVD_SUBMIT_RING_SIZE"] = "8"
    ring_engine = NativeEngine(executor=LocalExecutor(), cycle_time_s=0.002,
                               stall_warning_s=0.0)
    ring_threads = [threading.Thread(target=ring_hammer,
                                     args=(ring_engine, t, 20, errors))
                    for t in range(4)]
    for t in ring_threads:
        t.start()
    for _ in range(50):
        ring_engine._collect_stats()
    for t in ring_threads:
        t.join()
    ring_engine.shutdown()

    if errors:
        print("\n".join(errors))
        return 1
    print("TSAN_SMOKE_OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
