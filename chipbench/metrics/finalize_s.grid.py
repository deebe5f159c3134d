"""Host seconds per grid call spent turning outputs into results (seconds).

The child span ``simulate_grid.finalize`` of the newest ``simulate_grid``
call (``repro.spans``), whose count ``jobs`` is the completion times it
produced: one ``SimResult`` per run, built on the host while the chip
waits.  Moves ``run_slots_per_s``.

Read in the process that ran the window, beside the device idle time
it accounts for: a trace that holds no device, or a program without the
span, gives nothing.
"""


def read(ctx):
    if not ctx["trace"]["devices"]:
        return None
    try:
        from repro import spans
    except ImportError:
        return None
    record = spans.last("simulate_grid")
    if record is None:
        return None
    return record.children.get("simulate_grid.finalize")
