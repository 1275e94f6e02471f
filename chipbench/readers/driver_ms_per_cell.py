"""Sweep driver (``repro.scenarios.worker``: claims, leases, store):
host milliseconds of the window outside ``synthesize_cell`` and
``run_packed``, per cell completed."""


def read(obs):
    if not obs["cells"]:
        return None
    host = obs["host"]
    inside = host["synthesize_cell"]["seconds"] + host["run_packed"]["seconds"]
    return 1e3 * (obs["window_s"] - inside) / obs["cells"]
