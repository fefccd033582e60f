"""transfer_ms: transfer-and-combine busy time (ms), host clock, as
the trainer measures it (``t_tran``), mean over the window's iterations."""


def read(rec):
    return 1e3 * rec["stages"]["transfer_s"]
