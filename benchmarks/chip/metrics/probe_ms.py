"""Host seconds of the probe stage per batch, in milliseconds: the
wrapped call's clock summed over the window's batches, over their
count. Batches the probe skips count as zero, so this is the amortized cost."""


def read(run):
    if not run.batches:
        return None
    total = sum(b["probe_s"] for b in run.batches)
    return 1000.0 * total / len(run.batches)
