"""The device's idle share of the traced window, in %: 1 minus the
union of the device operations' intervals over the window."""


def read(spec, run):
    trace = run["trace"]
    if not trace or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
