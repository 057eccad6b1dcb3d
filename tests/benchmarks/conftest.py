"""What `bench_toy.cell` leaves out, until a `benchmark` PR puts it there.

`bench_toy.py` turns a serving mix's `min` and `max` down for the CPU
tests and leaves its `step`. A mix on a grid coarser than the toy lengths
(the long-context cell's prompts lie on a grid of 256, as its issue fixed
them) then has no reachable length at toy size and
`loadgen.reachable_shapes` raises. No PR but a `benchmark` PR may edit
`bench_toy.py`, so the grid is turned down here, around its `cell`, for
every test module that imports it: only where a step is coarser than the
toy lengths' 16, so the cells the benchmark had run at toy size as they
did. The repair belongs in `bench_toy.cell` (one line beside `min` and
`max`); with it this file goes (ROADMAP B0 h)."""

import bench_toy

TOY_STEP = 16

_cell = bench_toy.cell


def cell(name: str) -> dict:
    out = _cell(name)
    traffic = out["traffic"]
    if traffic["kind"] != "train":
        for key in ("prompt_len", "output_len"):
            if traffic[key].get("step", 1) > TOY_STEP:
                traffic[key]["step"] = TOY_STEP
    return out


bench_toy.cell = cell
