"""Set-up. Seconds JAX spent TRACING the cell's own step program (the serving
engines' `_ragged_fn`, the train runner's `train_step`:
`setup_record.STEP_PROGRAM`), summed over its compile records (`trace_s`, from
`/jax/core/compile/jaxpr_trace_duration`): the Python of the step run once to
a jaxpr, the inner `jit`s of `jnp` functions inside it. Warm or cold alike:
the persistent cache serves the compile, never the trace."""
import setup_record


def read(rec):
    found = setup_record.of(rec)
    return found and found.step_sum("trace_s")
