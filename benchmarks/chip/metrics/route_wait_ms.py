"""The consumer's time blocked on the route step per batch, in
milliseconds: the program's ``route.wait`` spans inside the traced
window, over the window's batches."""
import spantrace


def read(run):
    return spantrace.per_batch_ms(run, lambda s: s.total_s("route.wait"))
