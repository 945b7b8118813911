"""Test fixture reader: milliseconds of ``paged_flash_decode`` self-time in
the trace per decode step that the engine counted over the window, from a
scope table entry of the trace and the two ends of the engine's stats."""


def read(ctx):
    red, st = ctx["trace"], ctx["stats"]
    t = red.get("scopes", {}).get("jit__lambda", {}).get("paged_flash_decode")
    steps = st["end"]["decode_steps"] - st["start"]["decode_steps"]
    if t is None or steps <= 0:
        return None
    return 1e3 * t / steps
