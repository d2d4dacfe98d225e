"""Set-up seconds: process start to the first timed event or step."""


def read(rec):
    return rec["setup_s"]
