"""train_accel_ms: the accelerator trainer's step (ms), host clock,
as the trainer measures it (``t_ta``), mean over the window's iterations."""


def read(rec):
    return 1e3 * rec["stages"]["train_accel_s"]
