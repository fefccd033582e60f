"""sample_ms: sampling busy time (ms), host clock, as the trainer
measures it (``t_sa + t_sc``), mean over the window's iterations."""


def read(rec):
    return 1e3 * rec["stages"]["sample_s"]
