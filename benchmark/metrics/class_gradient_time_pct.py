"""Device time under the scope `gradients` over busy time: in a
multiclass step, the softmax over the (k, N) score and the g/h it
writes, once an iteration for every class."""
from benchmark import phases


def read(run):
    return phases.scope_pct(run, "gradients")
