"""The share of the tokens the step programs produced that the engine threw
away, in %: `decode.tokens_discarded` (tokens past a request's
`max_new_tokens` or its end, and every token of a row dispatched on
speculation for a request that had ended) over those and the delivered
`decode.tokens`. Device work that bought nothing. None on a program that
drafts nothing."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    if ctx.kind != "serve" or not c.get("decode.draft_proposed"):
        return None
    thrown = c.get("decode.tokens_discarded", 0)
    made = thrown + c.get("decode.tokens", 0)
    return 100.0 * thrown / made if made else None
