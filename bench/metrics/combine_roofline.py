"""combine_roofline: the combine's share of its bandwidth roofline (%):
the bytes the layer-0 positions need (each written once and read once)
at the chip's HBM bandwidth, over the device time of the combine's
module in the traced window."""
from bench import flops, trace

MODULES = ("_assemble_tiled_device",)


def read(rec):
    summary, peaks = rec["trace"], rec["peaks"]
    if summary is None or peaks is None:
        return None
    seconds = trace.module_seconds(summary, MODULES)
    if seconds <= 0 or rec["traffic"]["positions"] <= 0:
        return None
    need = flops.combine_bytes(rec["traffic"]["positions"], rec["feat_dim"])
    return 100.0 * need / peaks["hbm_bytes_per_s"] / seconds
