"""compiles_in_window: backend compiles during the window."""


def read(rec):
    return rec["compiles_in_window"]
