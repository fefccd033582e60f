"""setup_s: seconds from process start to the start of the window (data
generation, trainer build, checked steps, warm-up, their compiles)."""


def read(rec):
    return rec["setup_s"]
