"""Set-up: from the start of the process to the window's opening
(weights, warm-up, compiles or cache loads)."""


def read(run):
    return run.setup_s
