"""cache_hit_rate: layer-0 positions of the accelerator trainers served
by the device cache over all positions looked up in the window (%)."""


def read(rec):
    t = rec["traffic"]
    if not rec["has_cache"] or t["positions"] <= 0:
        return None
    return 100.0 * t["hit_rows"] / t["positions"]
