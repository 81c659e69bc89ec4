"""1 - union of the device's operation intervals over the traced window."""


def read(ctx):
    d = ctx["device"]
    if "busy_s" not in d:
        return None
    return 100.0 * (1.0 - d["busy_s"] / d["window_s"])
