"""The one rule for ending a process that may hold chips, memory or a
socket: whoever ends it waits until it is gone.

A chip belongs to the process that opened it until the kernel has torn
that process down, and the next holder's libtpu cannot open it before
("open(/dev/vfio/N): Device or resource busy"). An ender that signals and
walks away hands that race to whatever starts next on the machine.

Each layer's deadline is derived from the layer below it; none is a flag.
"""

from __future__ import annotations

import ctypes
import logging
import signal
import subprocess
import time
from typing import Iterable, List, Optional

logger = logging.getLogger(__name__)

# A worker told to exit tries its clean shutdown and leaves by os._exit
# after this long whatever the shutdown does (a user task blocked in
# get() against a dying cluster wedges it). A clean one takes 0.03 s.
WORKER_EXIT_S = 1.0
# What an ender gives a SIGTERMed process before SIGKILL: the worker's own
# backstop and half a second, so that the backstop fires first and SIGKILL
# is for the process that cannot run a handler at all.
GRACE_S = WORKER_EXIT_S + 0.5
# How long a SIGKILLed process may take to be reaped. The kernel took
# 3-13 s over a worker that held four chips with 11.46 GB on each (PR 46,
# chip run); where nothing is held it takes milliseconds.
GONE_S = 30.0
# What the ender of a raylet gives it before SIGKILL: what the raylet may
# need for its own workers, and a margin for closing its store and server.
RAYLET_GRACE_S = GRACE_S + GONE_S + 2.0

_libc = ctypes.CDLL(None)
_PR_SET_PDEATHSIG = 1


def die_with_parent() -> None:
    """A `preexec_fn`: the kernel SIGKILLs the child when the thread that
    spawned it dies, however it dies. Survives the exec."""
    _libc.prctl(_PR_SET_PDEATHSIG, signal.SIGKILL)


def _wait(procs: List[subprocess.Popen], seconds: float
          ) -> List[subprocess.Popen]:
    deadline = time.monotonic() + seconds
    still = []
    for proc in procs:
        try:
            proc.wait(timeout=max(0.0, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            still.append(proc)
    return still


def end_processes(procs: Iterable[subprocess.Popen],
                  grace_s: Optional[float] = None,
                  gone_s: Optional[float] = None) -> List[subprocess.Popen]:
    """SIGTERM to all that live, one shared `grace_s`, SIGKILL to the rest,
    then wait until every one has been reaped, `gone_s` at the most.
    Returns (and logs) whatever is still there. Blocking: call it off an
    event loop that must keep serving."""
    live = [p for p in procs if p.poll() is None]
    for proc in live:
        proc.terminate()
    live = _wait(live, GRACE_S if grace_s is None else grace_s)
    for proc in live:
        proc.kill()
    live = _wait(live, GONE_S if gone_s is None else gone_s)
    for proc in live:
        logger.warning("pid %d still alive after SIGKILL and the wait: %s",
                       proc.pid, proc.args)
    return live
