"""Device. `memory_stats()["bytes_in_use"]` over `bytes_limit`, read when
the window closes: what the window holds (weights and the whole reserved
pool, or the training state), beside `peak_mem_share`, which in serving is
the engine's build and not the window."""


def read(rec):
    mem = rec.get("memory") or {}
    if not mem.get("bytes_limit") or "bytes_in_use" not in mem:
        return None
    return 100.0 * mem["bytes_in_use"] / mem["bytes_limit"]
