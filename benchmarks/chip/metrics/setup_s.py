"""Process start until the window opens: load, warm-up, pre-roll (s)."""


def read(rec):
    return rec["setup_s"]
