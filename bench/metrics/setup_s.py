"""Process start to window open: generating, ingesting, registering,
compiling or loading compiled code, and the warm-up traffic."""


def read(run):
    return run.setup_s
