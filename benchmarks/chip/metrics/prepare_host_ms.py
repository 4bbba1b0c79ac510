"""The prepare stage's time on the host per batch, in milliseconds: the
program's ``prepare`` spans inside the traced window less their
``prepare.wait`` (the prefetch thread blocked on the device), over the
window's batches."""
import spantrace


def read(run):
    return spantrace.per_batch_ms(
        run, lambda s: s.total_s("prepare") - s.total_s("prepare.wait"))
