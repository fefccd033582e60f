"""update_ms: gradient sync and optimizer update (ms), host clock, as
the trainer measures it (``t_sync``), mean over the window's iterations."""


def read(rec):
    return 1e3 * rec["stages"]["update_s"]
