"""All tokens of all steps finished in the window, over the window, over
the chips; the window opens and closes at flushes of `fit`."""


def read(run):
    if run["kind"] != "train_steps" or run["window_s"] <= 0:
        return None
    return run["tokens"] / run["window_s"] / run["chips"]
