"""A number the run itself took: ``setup_s`` (process start to the first
timed request) or ``window_compiles`` (programs built, compiled or loaded
from the persistent cache, inside the timed window)."""


def read(ctx, key):
    return float(ctx[key])
