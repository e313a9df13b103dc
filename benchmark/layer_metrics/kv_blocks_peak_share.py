"""Cache. Most blocks of the paged pool in use at any step's end, over the
pool's blocks."""


def read(rec):
    if not rec.get("kv_blocks"):
        return None
    return 100.0 * rec["kv_blocks_peak"] / rec["kv_blocks"]
