"""Process start -> the window opens: writing the weights, starting the
server, loading, warming up (compiling, in a checkout's first run) and the
correctness checks."""


def read(run):
    return run.setup_s
