"""Faults planted in the timed path underneath a built engine, to show
that ``correct`` catches each: a decode step that returns its state
unchanged, half of the decode batch left out, and a token altered where
it is produced.

``plant(eng, name)`` patches the engine's runner and returns a function
that undoes the patch.  The benchmark's own runs never plant one.
"""
from __future__ import annotations

from typing import Callable


def _unchanged(runner) -> None:
    step = runner.step

    def unchanged(tokens, positions, seg_kind, *, cache, **kw):
        logits, new = step(tokens, positions, seg_kind, cache=cache, **kw)
        return (logits, cache) if seg_kind == "decode" else (logits, new)
    runner.step = unchanged


def _half(runner) -> None:
    step = runner.step

    def half(tokens, positions, seg_kind, *, cache, **kw):
        logits, new = step(tokens, positions, seg_kind, cache=cache, **kw)
        if seg_kind == "decode":
            logits = logits.at[:logits.shape[0] // 2].set(0.0)
        return logits, new
    runner.step = half


def _altered(runner) -> None:
    sample = runner.sample

    def altered(key, logits, temps):
        toks, bad = sample(key, logits, temps)
        toks = toks.copy()
        toks[0] = (toks[0] + 1) % logits.shape[-1]
        return toks, bad
    runner.sample = altered


FAULTS: dict[str, Callable] = {"unchanged": _unchanged, "half": _half,
                               "altered": _altered}


def plant(eng, name: str) -> Callable[[], None]:
    """Plant fault ``name`` in ``eng``'s runner; returns the undo."""
    runner = eng.runner
    saved = runner.step, runner.sample
    FAULTS[name](runner)

    def undo() -> None:
        runner.step, runner.sample = saved
    return undo
