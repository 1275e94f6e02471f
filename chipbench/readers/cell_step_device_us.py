"""Cell program (``accel.cell_step``'s jitted chunks): device busy
microseconds of the traced window per row of a batch per scan step
executed (rows x chunk length x chunks, counted around ``run_packed``).
All device work of the served path is issued from ``run_packed``."""


def read(obs):
    trace = obs["trace"]
    if trace is None or not obs["rows_steps"] or trace["busy_s"] <= 0.0:
        return None
    return 1e6 * trace["busy_s"] / obs["rows_steps"]
