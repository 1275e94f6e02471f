"""Cell program: share of the row-steps the device scanned (rows x
chunk length x chunks) that were event steps of a cell still running,
in percent, over the window's ``run_packed`` calls, from the program's
own counters (``repro.accel.cell_step.RUN_LOG``: the window's calls are
its last ``run_packed`` calls).  The rest are no-op steps of finished
cells and of a last chunk's tail.  A program without the log gives
nothing to read."""


def read(obs):
    calls = obs["host"]["run_packed"]["calls"]
    try:
        from repro.accel.cell_step import RUN_LOG
    except ImportError:
        return None
    records = list(RUN_LOG.records)
    if not calls or len(records) < calls:
        return None
    window = records[-calls:]
    return (100.0 * sum(r["event_steps"] for r in window)
            / sum(r["scanned_row_steps"] for r in window))
