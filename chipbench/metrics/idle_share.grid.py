"""Share of the traced window in which the traced chip ran no operation (%).

Busy time is the union of the device's operation intervals in the window
(``chipbench/trace.py``).  Moves ``run_slots_per_s``: time the chip idles
between and around the grid's scans is time no run advances.
"""


def read(ctx):
    red = ctx["trace"]
    if not red["devices"] or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
