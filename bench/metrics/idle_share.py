"""Shared by the device_idle_share.* readers: 100 * (1 - busy / window)
from the device trace."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100
