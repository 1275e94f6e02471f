"""Cell program: event steps each cell took, over the window's
``run_packed`` calls, from the program's own counters
(``repro.accel.cell_step.RUN_LOG``, one record per call: the window's
calls are its last ``run_packed`` calls).  A program without the log
gives nothing to read."""


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
    return (sum(r["event_steps"] for r in window)
            / sum(r["rows"] for r in window))
