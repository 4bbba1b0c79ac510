"""Host seconds of the complete stage per batch, in milliseconds: the
wrapped call's clock summed over the window's batches, over their
count. The quality probe runs inside complete; its time is left out here."""


def read(run):
    if not run.batches:
        return None
    total = sum(b["complete_s"] for b in run.batches)
    return 1000.0 * total / len(run.batches)
