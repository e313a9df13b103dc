"""Set-up. Seconds from the first to the last line of `paddle_tpu/__init__.py`
(monitor `startup.import_s`, stamps `startup.import`): JAX's own import where
the package is the first to import it, the package's modules, every Pallas
kernel module. What comes before it (the interpreter, the benchmark's own
imports) and after it (the runtime's start) is `setup_s`'s, not this."""
import setup_record


def read(rec):
    found = setup_record.of(rec)
    return found and found.value("startup.import_s")
