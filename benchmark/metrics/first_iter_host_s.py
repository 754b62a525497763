"""Host time of the first iteration that is tracing, lowering, compiling
and loading from the compile cache, as JAX reports them inside the
program's iteration 0."""
from benchmark import phases


def read(run):
    return phases.first_iter_host_s()
