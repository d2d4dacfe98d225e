"""Server events committed over the window's seconds (host clock)."""


def read(rec):
    if rec["unit"] != "events":
        return None
    return rec["work"] / rec["window_s"]
