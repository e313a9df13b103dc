"""Set-up. Seconds JAX spent LOWERING the cell's own step program
(`setup_record.STEP_PROGRAM`) from its jaxpr to an MLIR module, summed over its
compile records (`lower_s`, from
`/jax/core/compile/jaxpr_to_mlir_module_duration`). Paid warm and cold alike:
the cache's key is computed from the module."""
import setup_record


def read(rec):
    found = setup_record.of(rec)
    return found and found.step_sum("lower_s")
