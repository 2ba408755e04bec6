"""``kv_page_run_pct.steady``: the share of live pages the decode kernel
copies in runs, from the two counts on ``engine.dispatch_window``."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from cells.run import reader  # noqa: E402

read = reader("layer_metrics", "kv_page_run_pct.steady")


def _ctx(stats):
    return {"trace": object(), "spans": {"engine#1": [
        ["engine.dispatch_window", 10 * i, 5, s] for i, s in enumerate(stats)]}}


def test_the_share_is_summed_over_the_traced_windows():
    ctx = _ctx([{"k": 16, "pages_live": 4000, "pages_in_runs": 3900},
                {"k": 16, "pages_live": 1000, "pages_in_runs": 600}])
    assert read(ctx) == pytest.approx(90.0)


def test_an_engine_that_counts_no_pages_reads_nothing():
    assert read(_ctx([{"k": 16, "live_tokens": 70_000}])) is None
    assert read(_ctx([{"pages_live": 0, "pages_in_runs": 0}])) is None
    assert read({"trace": None}) is None
