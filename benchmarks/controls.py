"""Solvers put in the sender's place to show that ``correct`` fails.

``EasierTargets`` is the control: the program's own solver ladder, on
the chip, with the same kernels and shapes, handed targets twice as
easy — the tempting step below the difficulty the configuration
states.  Half the work, and about half its nonces miss the network's
target.  ``SpoiledNonces`` breaks the timed path where an answer is
produced: every nonce comes back one too high.
"""

from __future__ import annotations

_MASK64 = (1 << 64) - 1


class _Wrapped:
    """Passes everything through until the harness arms it at the start
    of the measured window, so that set-up stays sound."""

    def __init__(self, inner):
        self.inner = inner
        self.armed = False

    def arm(self) -> None:
        self.armed = True

    def __getattr__(self, name):        # breakers, last_backend, ...
        return getattr(self.inner, name)

    def _target(self, target: int) -> int:
        return target

    def _result(self, result):
        return result

    def __call__(self, initial_hash, target, **kw):
        return self._result(self.inner(initial_hash,
                                       self._target(target), **kw))

    def solve_batch(self, items, **kw):
        items = [(ih, self._target(t)) for ih, t in items]
        return [self._result(r)
                for r in self.inner.solve_batch(items, **kw)]


class EasierTargets(_Wrapped):
    FACTOR = 2

    def _target(self, target: int) -> int:
        if not self.armed:
            return target
        return min(target * self.FACTOR, _MASK64)


class SpoiledNonces(_Wrapped):
    def _result(self, result):
        nonce, trials = result
        if not self.armed:
            return result
        return (nonce + 1) & _MASK64, trials
