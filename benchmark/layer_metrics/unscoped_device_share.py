"""Device. Device time of the operations whose scope path names no region
of the program and no kernel (no `tf_op` at all, or only JAX's own
components) over the device's busy time. The log gets the device time by
region and the largest unscoped operations."""
import program_trace
import trace_reduce


def read(rec):
    def unscoped(scope):
        return not (program_trace.region(scope) or program_trace.kernel(scope))

    value = program_trace.share(rec, unscoped)
    if value is None:
        return None
    pt, tr = program_trace.of(rec), rec["trace"]
    table = sorted(pt.by_region(tr["ops"]).items(), key=lambda kv: -kv[1])
    print("    device time by region, % of busy: " + ", ".join(
        f"{k} {100.0 * v / tr['busy_s']:.2f}" for k, v in table), flush=True)
    loose = sorted(((sec, name) for name, sec in tr["ops"].items()
                    if unscoped(pt.scopes.get(name, ""))), reverse=True)[:12]
    print("    largest unscoped operations, s [scope path]: " + "; ".join(
        f"{trace_reduce.label(name)} {sec:.4f} "
        f"[{pt.scopes.get(name) or 'none'}]" for sec, name in loose),
        flush=True)
    return value
