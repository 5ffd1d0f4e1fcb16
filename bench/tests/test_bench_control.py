"""The correctness check fails what it must, at the tiny size.

The control (the reference in float8), judged by the check's own
comparison, comes out not correct where the program comes out correct,
and a run of the harness with the
timed path broken underneath comes out not correct: a decoded token
altered where it is produced, and a decode step that returns the KV pages
unchanged. The harness's look for a chip is skipped; the rest of a run is
driven as the benchmark drives it.
"""
import pytest
import tiny

from harness.cell import Setup, verify
from harness.spec import load_cell
from repro.serving.paged_engine import PagedModelRunner


@pytest.fixture(scope="module")
def dense_root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("root")),
                          model=tiny.DENSE)


@pytest.mark.parametrize("seed", [11, 14])
def test_control_fails_where_the_program_passes(dense_root, seed):
    cell = load_cell("tiny.chat", dense_root)
    s = Setup(cell, seed, require_chip=False)
    ctx = s.window(cell.traffic, 3.0)
    s.free()
    ok, _, readings, control_ok = verify(ctx, seed, control=True)
    assert ok and control_ok is False
    for k, limit in tiny.DENSE["check"]["limits"].items():
        assert readings[k] <= limit


def _token_altered(orig):
    def decode(self, *a, **kw):
        logits, pages, stats = orig(self, *a, **kw)
        return logits.at[:, 0].add(1e4), pages, stats
    return decode


def _state_unchanged(orig):
    def decode(self, tokens, pages, *a, **kw):
        logits, _, stats = orig(self, tokens, pages, *a, **kw)
        return logits, pages, stats
    return decode


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_broken_timed_path_is_not_correct(dense_root, monkeypatch, fault):
    monkeypatch.setattr(PagedModelRunner, "decode",
                        fault(PagedModelRunner.decode))
    res = tiny.run_tiny(dense_root, seed=11)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"]


def test_in_flight_requests_are_checked(tmp_path):
    """Requests still decoding when the window closes are compared over
    the tokens served to them so far."""
    import io
    mix = dict(tiny.BACKLOG, output_len={"dist": "uniform", "min": 400,
                                         "max": 500})
    root = tiny.make_root(str(tmp_path), model=tiny.DENSE,
                          mixes={"long": mix})
    cell = load_cell("tiny.long", root)
    s = Setup(cell, 12, require_chip=False)
    ctx = s.window(mix, 2.0)
    s.free()
    assert not any(r.req.finish_time >= 0 for r in ctx.records)
    log = io.StringIO()
    ok, _, _, _ = verify(ctx, 12, log=log)
    assert ok
    served = int(log.getvalue().split()[3])
    assert served >= tiny.DENSE["check"]["min_served"]
