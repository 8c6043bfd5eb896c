"""The decode-step program in the traced window, by its own name
(`DecodeEngine._entry` names it ``decode_step_b<bucket>``): not "the
program that held the device longest" (`_trace.main_program`), which in a
start-up wave is a prefill."""


def decode_step(ctx):
    """{"runs", "seconds"} of the decode-step program (the busiest bucket's
    if there are several), or None where the trace holds none."""
    found = [p for name, p in ((ctx.trace or {}).get("programs")
                               or {}).items() if "decode_step" in name]
    return max(found, key=lambda p: p["seconds"]) if found else None
