"""One runner per kind of configuration (`train`, `serve`): `kind` says how
a run is driven and counted. What model a serving run serves is its
`family`'s to say (../families/).

A runner loads, warms, opens the window, measures, closes it and returns a
`Result`: plain facts of the run, which the readers under ../readers/ turn
into metrics.
"""

import importlib
from types import SimpleNamespace


def load(kind: str):
    return importlib.import_module(f"{__name__}.{kind}")


def result(**fields) -> SimpleNamespace:
    """The runner's facts. Every reader gets this object as `ctx`; a field a
    runner does not fill is None, and a reader that finds None returns None."""
    base = dict(
        kind=None, correct=False, attempted=0, failed=0, notes=[],
        compared=[],      # [name, value, limit] of each number the check held
        setup_s=None, window_s=None, chips=1, device=None, peaks=None,
        config=None, traffic=None, cache=None, peak_hbm_bytes=None,
        window_hbm_bytes=None,
        # training
        steps=None, tokens_per_step=None, first_step_s=None,
        step_ms=None, dispatch_ms=None, flops_per_token=None,
        # serving
        requests=None, answered=None, window=None, telemetry=None,
        step_bytes=None, live_context_tokens=None,
        # traced runs
        trace=None)
    base.update(fields)
    return SimpleNamespace(**base)
