"""Worker process entry point.

Reference equivalent: `python/ray/_private/workers/default_worker.py` +
`Worker.main_loop` (`_private/worker.py:799`): construct the core-worker
runtime in worker mode, register with the raylet, and serve task pushes
until told to exit.

Round 10: a worker is no longer a pure RPC server. When its lease's
driver attaches a worker-direct dispatch ring (`submit_ring` mode,
`cluster_runtime.handle_attach_task_ring`), the runtime's event loop
also consumes task-spec deltas straight off the shared-memory ring —
doorbell-fd wakeups plus an adaptive backstop poll — and feeds them
through the same `_execute_task` path the RPC pushes take, with replies
riding the twin ring. Steady state, dispatch costs this process zero
syscalls per task in each direction.
"""

from __future__ import annotations

import argparse
import logging
import signal
import sys
import threading


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--raylet", required=True)
    parser.add_argument("--gcs", required=True)
    parser.add_argument("--worker-id", required=True)
    parser.add_argument("--node-id", required=True)
    args = parser.parse_args()

    logging.basicConfig(
        level=logging.INFO,
        format=f"[worker {args.worker_id[:8]}] %(message)s")

    # SIGUSR1 dumps all thread stacks to stderr (the worker log file):
    # the debugging affordance for "worker stuck in what?" (reference:
    # ray stack / py-spy integration).
    import faulthandler

    faulthandler.register(signal.SIGUSR1, all_threads=True, chain=False)

    from ray_tpu.core.cluster_runtime import ClusterRuntime
    from ray_tpu.core.worker import set_runtime

    runtime = ClusterRuntime(
        gcs_address=args.gcs, raylet_address=args.raylet, mode="worker",
        node_id=args.node_id, worker_id=args.worker_id)
    set_runtime(runtime)

    ok = runtime._loop.run(runtime._raylet.call(
        "register_worker", worker_id=args.worker_id,
        address=runtime.address))
    if not ok:
        logging.error("raylet rejected registration; exiting")
        sys.exit(1)

    # No watch on the raylet here: a worker does not outlive its raylet
    # because the kernel SIGKILLs it when the raylet dies (the raylet
    # spawns it with procs.die_with_parent), also in the middle of the
    # start-up above.
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    stop.wait()
    # Graceful shutdown can wedge on non-daemon task threads (a user task
    # blocked in get() against a dying cluster); the process must still
    # exit before its ender's grace runs out. Arm a hard-exit backstop,
    # attempt the clean path, then force the issue.
    import os

    from ray_tpu.core.procs import WORKER_EXIT_S

    killer = threading.Timer(WORKER_EXIT_S, lambda: os._exit(1))
    killer.daemon = True
    killer.start()
    try:
        runtime.shutdown()
    except BaseException:
        logging.exception("shutdown failed")
        os._exit(1)
    os._exit(0)


if __name__ == "__main__":
    main()
