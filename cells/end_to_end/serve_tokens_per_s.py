"""Output tokens of completed requests / the window, closed loop, not
streamed.  A request's tokens count by the share of its time in the system
that lies inside the window (``loadgen.tokens_in_window``), so the run
waits for what was in flight at the window's end.  (Counting a request
whole at its completion spread 7.5% between runs: a few 512-token answers
ending just inside or outside the window; PERF.md section 6.)"""


def read(ctx):
    run = ctx["run"]
    if run["kind"] != "serve":
        return None
    return run["reduced"]["output_tokens"] / run["window_s"]
