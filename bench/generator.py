"""The one traffic generator: closed-loop analysts from a mix's parameters.

A mix file (``bench/traffic/<name>.json``) holds only parameters; every mix
is read by this module, which has no branch on a mix's name.  Each analyst
waits for the result of an event before acting again (a closed loop) and
acts independently of the others.  An analyst works in *episodes*: it picks
a dimension, then sends ``episode_steps`` events on it, ``step_gap_s`` after
each result, and with ``backtrack`` one more that clears the dimension's
filter (back to the view without it); between episodes it thinks for an
exponentially distributed time with mean ``think_mean_s``.

Parameters:

- ``analysts``: number of concurrent sessions
- ``dims``: ``"ordered"`` (range brushes only) or ``"all"`` filterable
  dimensions of the configuration
- ``step``: ``"shift"`` moves the episode's range by one bin per step (a
  drag, bouncing at the domain's ends); ``"jump"`` draws a fresh range or
  IN-list on each step
- ``episode_steps``: ``[lo, hi]``, steps per episode, uniform
- ``backtrack``: end every episode with a clear of its dimension
- ``step_gap_s``, ``think_mean_s``: waits after a result, in seconds
- ``think_strata``: think times are the ``(i + 0.5) / k`` quantiles of the
  exponential, ``k`` of them in a shuffled cycle
- ``range_frac``: ``[lo, hi]``, range width as a share of the domain
- ``in_values``: ``[lo, hi]``, size of an IN-list
- ``warmup_sweeps``, ``warmup_events``: the warm-up, below

The seed draws everything an analyst does: which dimension each episode
takes, how long it is, how long the analyst thinks before it, where each
range lies and which values an IN-list holds.  Dimensions, episode lengths
and think times are drawn in shuffled rounds of a fixed multiset, so every
seed does nearly the same work, in another order.  The rounds of dimensions
are the same for every analyst of a run, each analyst turned one place
further along them, so analysts in step (as in a mix with no think time)
brush different dimensions, and every step of the server meets nearly the
same mix of dimensions whatever the seed.  The window and the
warm-up draw from streams of their own (``WINDOW_STREAM``,
``WARMUP_STREAM``).

The warm-up is a fixed part and a drawn part.  The fixed part, the
*sweep* (:func:`sweeps`), is the same for every seed: every analyst, in
step with the others and with the same values, takes each of the mix's
dimensions in the configuration's order for one episode of two steps (and
its backtrack), ``warmup_sweeps`` times over.  It reaches every combination of filtered
attributes a window can hold: one dimension at a time with ``backtrack``,
all of them once every dimension has been brushed without it.  The drawn
part is ``warmup_events`` events per analyst of the cell's own traffic
from the warm-up stream.  Which analysts' events share a step, and so which
compiled batch a step needs, the sweep does not enumerate; what the drawn
part misses compiles in the window and is counted there.

Events are plain dicts: ``{"op": "set", "attr", "lo", "hi", "values",
"source"}`` or ``{"op": "clear", "attr"}``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

WINDOW_STREAM, WARMUP_STREAM = 0, 1
SWEEP = 0x5C41  # seeds the sweep's values, the same for every run


@dataclasses.dataclass
class Step:
    event: dict
    delay_s: float  # wait after the previous result before this event is due


class _Cycle:
    """Draws from a fixed multiset in shuffled rounds, each round's order
    turned ``shift`` places (in rounds of the reversed order without
    ``rng``)."""

    def __init__(self, items, rng: np.random.Generator | None, shift: int = 0):
        self.items, self.rng, self.shift, self.left = list(items), rng, shift, []

    def next(self):
        if not self.left:
            n = len(self.items)
            order = range(n) if self.rng is None else self.rng.permutation(n)
            order = [order[(k + self.shift) % n] for k in range(n)]
            self.left = [self.items[i] for i in order]
        return self.left.pop()


def mix_dims(mix: dict, cfg: dict) -> list[dict]:
    """The configuration's filterable dimensions the mix brushes, in order."""
    dims = [f for f in cfg["filters"] if f["ordered"] or mix["dims"] == "all"]
    return [f for f in dims if f["ordered"]] if mix["step"] == "shift" else dims


class Analyst:
    """One analyst's event stream for a mix over a configuration."""

    def __init__(self, mix: dict, cfg: dict, seed: int, stream: int, index: int):
        self.mix = mix
        self.doms = cfg["domains"]
        self.rng = np.random.default_rng(np.random.SeedSequence([int(seed), stream, index]))
        # every analyst draws the same rounds of dimensions, turned by its
        # index: at any moment the analysts spread over the dimensions alike
        shared = np.random.default_rng(np.random.SeedSequence([int(seed), stream]))
        self.dims = _Cycle(mix_dims(mix, cfg), shared, index)
        lo, hi = mix["episode_steps"]
        self.steps = _Cycle(range(lo, hi + 1), self.rng)
        k = int(mix.get("think_strata", 1))
        mean = float(mix["think_mean_s"])
        self.thinks = _Cycle([-mean * np.log(1 - (i + 0.5) / k) for i in range(k)], self.rng)
        self.left = 0
        self.backtrack = False

    def _range(self, d: int) -> tuple[int, int]:
        lo, hi = self.mix["range_frac"]
        width = int(self.rng.integers(max(1, round(lo * d)), max(1, round(hi * d)) + 1))
        width = min(width, d - 1) if d > 1 else 1
        start = int(self.rng.integers(0, d - width + 1))
        return start, start + width

    def _fresh(self, dim: dict) -> dict:
        d = self.doms[dim["attr"]]
        ev = {"op": "set", "attr": dim["attr"], "source": dim["source"],
              "lo": None, "hi": None, "values": []}
        if dim["ordered"]:
            ev["lo"], ev["hi"] = self._range(d)
        else:
            lo, hi = self.mix["in_values"]
            k = int(self.rng.integers(lo, min(hi, d - 1) + 1))
            ev["values"] = sorted(int(v) for v in self.rng.choice(d, k, replace=False))
        return ev

    def _shift(self, ev: dict) -> dict:
        d = self.doms[ev["attr"]]

        def fits(move: int) -> bool:
            return 0 <= ev["lo"] + move and ev["hi"] + move <= d

        if not fits(self.direction):
            self.direction = -self.direction
        move = self.direction if fits(self.direction) else 0
        return dict(ev, lo=ev["lo"] + move, hi=ev["hi"] + move)

    def _episode(self, dim: dict, steps: int) -> float:
        """Starts an episode on ``dim``; returns the wait before its first step."""
        self.dim, self.left = dim, steps
        self.backtrack = bool(self.mix.get("backtrack", False))
        self.direction = 1 if self.rng.random() < 0.5 else -1
        self.last = None
        return self.thinks.next()

    def next(self) -> Step:
        gap = float(self.mix["step_gap_s"])
        if self.left == 0 and self.backtrack:
            self.backtrack = False
            return Step({"op": "clear", "attr": self.dim["attr"]}, gap)
        delay = self._episode(self.dims.next(), self.steps.next()) if self.left == 0 else gap
        self.left -= 1
        self.last = self._fresh(self.dim) if self.last is None or self.mix["step"] == "jump" \
            else self._shift(self.last)
        return Step(dict(self.last), delay)


class Sweep(Analyst):
    """The warm-up's fixed part for one analyst (see the module's text)."""

    def __init__(self, mix: dict, cfg: dict, index: int):
        # every analyst the same values: their queries are identical, so the
        # server runs each once, whatever the number of analysts
        super().__init__(mix, cfg, SWEEP, WARMUP_STREAM, 0)
        self.order = _Cycle(mix_dims(mix, cfg), None)
        self.order.items.reverse()  # ``_Cycle`` pops from the end
        self.length = len(self.order.items) * int(mix["warmup_sweeps"]) \
            * (2 + bool(mix.get("backtrack", False)))

    def next(self) -> Step:
        if self.left == 0 and not self.backtrack:
            self._episode(self.order.next(), 2)
            self.left -= 1
            self.last = self._fresh(self.dim)
            return Step(dict(self.last), 0.0)
        step = super().next()
        step.delay_s = 0.0
        return step


def analysts(mix: dict, cfg: dict, seed: int, stream: int) -> list[Analyst]:
    return [Analyst(mix, cfg, seed, stream, i) for i in range(int(mix["analysts"]))]


def sweeps(mix: dict, cfg: dict) -> list[Sweep]:
    return [Sweep(mix, cfg, i) for i in range(int(mix["analysts"]))]
