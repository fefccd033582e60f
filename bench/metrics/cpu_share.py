"""cpu_share: the CPU trainer's share of the batch (%), mean over the
window of the DRM's assignment after each iteration."""


def read(rec):
    return 100.0 * rec["stages"]["cpu_share"]
