"""Device. `memory_stats()["peak_bytes_in_use"]` over `bytes_limit`, read
when the window closes."""


def read(rec):
    mem = rec.get("memory") or {}
    if not mem.get("bytes_limit"):
        return None
    return 100.0 * mem["peak_bytes_in_use"] / mem["bytes_limit"]
