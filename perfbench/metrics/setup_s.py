"""Set-up seconds: start-up, graphs, LPs, placement and every program loaded or compiled."""


def read(run):
    return run.setup_s
