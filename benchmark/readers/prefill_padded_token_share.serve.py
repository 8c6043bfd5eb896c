"""Share of the tokens the window's prefill programs computed that were
padding: 1 - `decode.prefill_tokens` (the prompts' own) over
`decode.prefill_bucket_tokens` (the buckets the prompts were padded to, or
the chunks run x the chunk), the ratio of useful to computed at the boundary
where the padding is decided. The prefill ops take no lengths: causality
keeps a padded tail out of real rows, and the tail is computed. None on a
program without the counter."""


def read(ctx):
    c = (ctx.telemetry or {}).get("counters") or {}
    asked, computed = (c.get("decode.prefill_tokens"),
                       c.get("decode.prefill_bucket_tokens"))
    if ctx.kind != "serve" or not computed or asked is None:
        return None
    return 100.0 * (1.0 - asked / computed)
