"""The share of drafted tokens the model accepted, in %: the engine's
`decode.draft_accepted` over `decode.draft_proposed` in the window (a model
with a draft module: a row's every step but its first proposes one draft;
serving/decode.py). With seeded weights the traffic's temperature sets it
(the configuration's `assumed.temperature`). None on a program that drafts
nothing."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    proposed = c.get("decode.draft_proposed")
    if ctx.kind != "serve" or not proposed:
        return None
    return 100.0 * c.get("decode.draft_accepted", 0) / proposed
