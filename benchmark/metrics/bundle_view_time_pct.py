"""Device time under the `bundle_view` scope (ops/grow.py
feature_hist_view on a bundled store: the gather of the group histograms
into one view a feature and the default bins rebuilt by subtraction, in
front of every split search) over busy time.  The scope sits inside
`split_search` and is taken out of it.  Nothing from a program that
declares no such scope."""
from benchmark import phases


def read(run):
    module = phases.timers()
    if module is None or "bundle_view" not in module.SCOPES:
        return None
    return phases.scope_pct(run, "bundle_view")
