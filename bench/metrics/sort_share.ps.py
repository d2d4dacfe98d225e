"""Share of the device's busy time spent in sort and top-k ops."""


def read(rec):
    t = rec["trace"]
    if not t or not t["sort_s"]:
        return None
    return 100.0 * t["sort_s"] / t["busy_s"]
