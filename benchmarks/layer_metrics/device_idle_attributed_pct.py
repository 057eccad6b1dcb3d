"""Device, serve: share of the traced window's device-idle time that lies
in the self time of a named span of the program (`rt:` spans other than
`engine.step`, whose self time is the loop with no phase named). Prints
the span table it rests on, a line of its own before the result line:
per span name the count, host seconds, self seconds and device-idle
seconds inside the self time; and the device programs' seconds by name."""

import json

from benchmarks.harness import program_trace


def read(ctx):
    reduction = program_trace.of_run(ctx)
    if not reduction or reduction["idle_attributed_share"] is None:
        return None
    table = {name: [s["count"], round(s["host_s"], 6), round(s["self_s"], 6),
                    round(s["device_idle_s"], 6)]
             for name, s in sorted(reduction["spans"].items(),
                                   key=lambda kv: -kv[1]["device_idle_s"])}
    print("program spans [count, host_s, self_s, device_idle_s]: "
          + json.dumps(table) + " modules: "
          + json.dumps(reduction["modules"]), flush=True)
    return 100.0 * reduction["idle_attributed_share"]
