"""The engine loop's clocks as a serving cell's counters carry them.

`InferenceEngine.stats()` gives `phase.<name>_s` (a partition of
`loop_s`, the loop's wall time), `thread_cpu_s`, `queue_wait_s`,
`stream_wake_s` and `stream_wake_tokens`, each fed by a `flight.span` in
the program; the cell's `snapshot()` takes every top-level number of
`stats()`, so `ctx["counters"]` holds their growth over the window. A
program that has no such clock (the parent of the PR that added them)
leaves the key out, and the readers then return None.
"""

from typing import Iterable, Optional

PREFIX = "phase."


def seconds(counters: dict, phase_names: Iterable[str]) -> Optional[float]:
    """Sum of the named phases' seconds; None if one is not there."""
    keys = [f"{PREFIX}{name}_s" for name in phase_names]
    if not keys or any(key not in counters for key in keys):
        return None
    return sum(counters[key] for key in keys)


def names(counters: dict) -> list:
    """Every phase the counters carry, without prefix and `_s`."""
    return [key[len(PREFIX):-len("_s")] for key in counters
            if key.startswith(PREFIX) and key.endswith("_s")]


def ms_per(counters: dict, secs: Optional[float], count_key: str
           ) -> Optional[float]:
    """`secs` over the growth of a counter, in milliseconds."""
    count = counters.get(count_key)
    if secs is None or not count:
        return None
    return secs / count * 1e3
