"""Host synthesis: calls of ``synthesize_cell`` per cell completed."""


def read(obs):
    if not obs["cells"]:
        return None
    return obs["host"]["synthesize_cell"]["calls"] / obs["cells"]
