"""Global batch tokens x steps completed in the window / the window, each
step ended by ``block_until_ready``, taken in the worker."""


def read(ctx):
    run = ctx["run"]
    if run["kind"] != "train":
        return None
    return run["tokens"] / run["window_s"]
