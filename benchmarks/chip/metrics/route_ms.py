"""Host seconds of the route stage per batch, in milliseconds: the
wrapped call's clock summed over the window's batches, over their
count."""


def read(run):
    if not run.batches:
        return None
    total = sum(b["route_s"] for b in run.batches)
    return 1000.0 * total / len(run.batches)
