"""train_cpu_ms: the CPU trainer's step (ms), host clock, as the
trainer measures it (``t_tc``), mean over the window's iterations."""


def read(rec):
    return 1e3 * rec["stages"]["train_cpu_s"]
