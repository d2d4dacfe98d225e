"""Share of the traced window in which no op ran on the device."""


def read(rec):
    t = rec["trace"]
    if not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
