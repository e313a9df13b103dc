"""Set-up. Seconds inside the serving engine's constructor (monitor
`engine.build_s`, stamps `engine.build`: parameters stacked, the pools made,
the step wrapped), less the compile records that lie inside it: the constructor's
own work, apart from what it made JAX compile."""
import setup_record


def read(rec):
    found = setup_record.of(rec)
    built = found and found.value("engine.build_s")
    if not built:
        return None
    return built - found.inside("engine.build")
