"""Share of the window's decode steps that were dispatched while the step
before them was not yet fetched (`decode.steps_ahead` over `decode.steps`):
under those the host's feed, launch, fetch and accept run while the device
has a step to work on; the rest were dispatched into an empty pipe, after an
admission or a batch that ended together. None on a program without the
counter: one whose loop fetches every step before it builds the next."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    steps, ahead = c.get("decode.steps"), c.get("decode.steps_ahead")
    if not steps or ahead is None:
        return None
    return 100.0 * ahead / steps
