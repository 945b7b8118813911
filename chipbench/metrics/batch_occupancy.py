"""Decode slots that produced a token, as a share of all slot-steps in the
window: tokens emitted over (ticks × slots), from the engine's own
counters (``EngineStats``). Moves ``output_tok_s``."""


def read(ctx):
    c = ctx["counters"]
    if not c["ticks"]:
        return None
    return 100.0 * c["tokens_out"] / (c["ticks"] * c["slots"])
