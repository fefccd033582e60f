"""load_ms: feature-load busy time (ms), host clock, as the trainer
measures it (``t_load``), mean over the window's iterations."""


def read(rec):
    return 1e3 * rec["stages"]["load_s"]
