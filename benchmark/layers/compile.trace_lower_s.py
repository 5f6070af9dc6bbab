"""Seconds JAX spent tracing and lowering during set-up: paid for every
shape in every process, whatever the persistent cache holds."""


def read(run):
    return run.jit.seconds("jaxpr_trace", t1=run.w0) \
        + run.jit.seconds("jaxpr_to_mlir_module", t1=run.w0)
