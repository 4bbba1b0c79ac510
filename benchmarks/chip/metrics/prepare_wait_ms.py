"""The prefetch thread's time blocked on the device for the features per
batch, in milliseconds: the program's ``prepare.wait`` spans inside the
traced window, over the window's batches."""
import spantrace


def read(run):
    return spantrace.per_batch_ms(run, lambda s: s.total_s("prepare.wait"))
