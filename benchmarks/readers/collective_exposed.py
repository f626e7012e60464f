"""Time in which chip 0 ran a collective and nothing else, as a share
of the traced stretch: the self time of collective operations on the
chip's operation line (an asynchronous collective's overlapped part is
not on that line, its ``-done`` wait is)."""


def read(ctx):
    t = ctx["trace"]
    if t.get("devices", 0) < 2 or not ctx.get("traced_s"):
        return None
    return 100.0 * t["collective_exposed_s"] / ctx["traced_s"]
