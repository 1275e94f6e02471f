"""Host synthesis (``accel.sweep_exec.synthesize_cell``): host
milliseconds inside it, per cell completed."""


def read(obs):
    if not obs["cells"]:
        return None
    return 1e3 * obs["host"]["synthesize_cell"]["seconds"] / obs["cells"]
