"""Cache. Most blocks of the WINDOW group's pool (the sliding-window layers')
in use at any counted step's end, over that pool's blocks: the cache
manager's own count (`BlockCacheManager.free_blocks_of`), read by the
runner's hook. It stays flat through a window (a lane holds a window's worth
and gives back a block for each it takes) while `kv_blocks_peak_share`, the
full layers' pool, grows with every token."""


def read(rec):
    if not rec.get("window_blocks"):
        return None
    return 100.0 * rec["window_blocks_peak"] / rec["window_blocks"]
