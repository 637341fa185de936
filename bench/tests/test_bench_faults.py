"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the server's step, where the answers are
produced, and the rest of a run goes on as usual (without the look for a
chip): an answer altered, and a re-render left out so a stale answer stays
on the analyst's screen.
"""

import dataclasses

import pytest

from bench.tests import tiny
from repro.serve.server import TreantServer


def _altered(results):
    viz = sorted(results)[0]
    r = results[viz]
    f = r.factor
    results[viz] = dataclasses.replace(r, factor=type(f)(f.attrs, f.field * 1.001 + 1.0, f.ring))


def _dropped(results):
    results.pop(sorted(results)[0])


@pytest.mark.parametrize("fault", [_altered, _dropped], ids=["answer_altered", "rerender_dropped"])
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    real = TreantServer.step

    def step(self):
        n = real(self)
        for handle in self._sessions.values():
            res = handle.last_result
            if res is not None and res.results and not getattr(res, "_faulted", False):
                fault(res.results)
                res._faulted = True
        return n

    monkeypatch.setattr(TreantServer, "step", step)
    out = tiny.run_tiny(monkeypatch, "taxi-7m.jump-16", seed=9, seconds=4.0)
    assert not out["correct"], out["readings"]
