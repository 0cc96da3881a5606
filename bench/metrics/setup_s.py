"""From the process's start to the window's opening: imports, data, the
model's state, compilation or the compile cache, warm-up, backlog."""


def read(run):
    return run.setup_s
