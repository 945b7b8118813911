"""Device time of the executables launched inside the engine's
``serve.admit`` span (prefill, KV page writes, pads) as a share of the
traced window's device busy time: the admission work every decode slot
waits behind. Moves ``itl_p99_ms``."""


def read(ctx):
    red = ctx["trace"]
    if not red or not red["devices"] or not red.get("by_span") \
            or red["busy_s"] <= 0:
        return None
    return 100.0 * red["by_span"].get("serve.admit", 0.0) / red["busy_s"]
