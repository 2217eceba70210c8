"""Every token generated inside the window over the window. The window's
edges are ends of engine calls (each ends in a device-to-host copy), so
the count is of whole calls: a decode call makes one token a live lane,
a prefill call one."""


def read(run):
    if run["kind"] != "closed_loop" or run["window_s"] <= 0:
        return None
    return run["generated_tokens"] / run["window_s"]
