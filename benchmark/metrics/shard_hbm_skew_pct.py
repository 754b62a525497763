"""The fullest device's `peak_bytes_in_use` over the emptiest's, less
one: 0 where every chip holds its share of the rows and nothing else.
From the program's last `mesh_memory` record, which a mesh learner's
booster writes where it brings the window's trees to the host: after the
window and before the reference, which runs on the first device alone,
touches the allocator's counters.  A program without the record (one
device, or the parent of the PR that brought it) and a backend that keeps
no such counter (the CPU) read as nothing."""
from benchmark import phases


def read(run):
    records = [r["fields"]["peak_bytes_in_use"] for r in phases.records()
               if r["kind"] == "count" and r["name"] == "mesh_memory"]
    if not records or len(records[-1]) < 2 or not min(records[-1]):
        return None
    return 100.0 * (max(records[-1]) / min(records[-1]) - 1.0)
