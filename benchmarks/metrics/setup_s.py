"""Process start to the opening of the window: import, weights on the
device, compile or cache load, warm-up of the cell's shapes."""


def read(run):
    return run["setup_s"]
