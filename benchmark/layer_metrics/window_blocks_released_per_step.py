"""Cache. Blocks the cache manager gave back behind the sliding window
(monitor counter `serving.kv.window_blocks_released`, its change over the
window) over the window's counted steps. Zero would mean the window group
only grows: the release is what lets its pool be a window's size."""


def read(rec):
    if not rec.get("window_blocks") or not rec.get("hook_steps"):
        return None
    return rec["window_blocks_released"] / rec["hook_steps"]
