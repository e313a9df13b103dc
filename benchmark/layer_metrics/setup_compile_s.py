"""Set-up. Seconds in JAX's `backend_compile` for the cell's own step program
(`setup_record.STEP_PROGRAM`), summed over its compile records (`backend_s`,
from `/jax/core/compile/backend_compile_duration`): the compile itself on an
empty cache (the log's record says `miss`), the read of the executable from
the persistent cache in a warm run (`hit`)."""
import setup_record


def read(rec):
    found = setup_record.of(rec)
    return found and found.step_sum("backend_s")
