"""Generation-2 garbage collections per batch, in milliseconds: the
program's ``gc`` spans inside the traced window (on any thread), over
the window's batches."""
import spantrace


def read(run):
    return spantrace.per_batch_ms(run, lambda s: s.total_s("gc"))
