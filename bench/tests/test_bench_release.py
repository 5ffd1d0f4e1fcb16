"""The wall-clock driver around the program's serving loop releases every
request once, in due order, and stamps its tokens on the host clock."""
import time

import numpy as np
import pytest
import tiny

from harness import traffic
from harness.cell import Setup
from harness.spec import load_cell


@pytest.mark.parametrize("preroll", [0.0, 1.5], ids=["cold", "preroll"])
def test_release_once_in_due_order(tmp_path, preroll):
    root = tiny.make_root(str(tmp_path))
    s = Setup(load_cell("tiny.chat", root), seed=3, require_chip=False)
    mix = dict(tiny.CHAT, rate_rps=4.0, preroll_s=preroll)
    t = time.perf_counter()
    ctx = s.window(mix, 3.0)
    s.free()
    due = [r for r in ctx.records if r.arrival.due_s < 3.0]
    assert len(due) == len(traffic.schedule(mix, 3.0)) >= 5
    released = [r.released for r in due]
    assert all(not np.isnan(x) for x in released)
    assert released == sorted(released)            # due order
    for r in due:                                  # never before due
        assert r.released >= ctx.t0 + r.arrival.due_s
        assert len(r.enqueued) == 1                # dispatched once
        assert r.enqueued[0] >= r.released
    done = [r for r in due if r.req.finish_time >= 0]
    assert done, "nothing finished in the window"
    for r in done:
        assert len(r.tokens) == len(r.req.output_tokens) == \
            r.req.max_new_tokens
        assert r.tokens == sorted(r.tokens) and r.tokens[0] > r.released
    assert ctx.t0 > t + preroll and ctx.compiles_in_window == 0
