"""Device time per scan step of the grid, per chip (microseconds).

The time the chip spent inside the grid program's ``while`` loops -- the
scan over slots, whose body is the vmapped slot step of
``slotted_sim._sim_core`` -- over the scan steps it ran.  The draws before
the scan and the completion scatter and sort after it are left out.  A
trace whose operations were cut short gives nothing.  Moves
``run_slots_per_s``.
"""


def read(ctx):
    red, steps = ctx["trace"], ctx["layers"]["steps"]
    if not red["ops_complete"] or red["loop_s"] <= 0 or steps <= 0:
        return None
    return 1e6 * red["loop_s"] / steps
