"""device_idle_pct — the share of the traced slice of the window in which
no operation ran on the device: 1 - (union of the device plane's
operation intervals) / (traced span). Nothing without a device trace."""


def read(ctx: dict):
    trace = ctx["trace"]
    if not trace:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
