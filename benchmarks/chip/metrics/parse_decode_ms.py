"""The parser model's greedy decode per batch, in milliseconds: the
program's ``parse.decode`` spans inside the traced window (every
slot's steps, the host waiting for the device at the end), over the
window's batches. A configuration without a parser model has nothing
to read."""
import spantrace


def read(run):
    if run.config.get("parser_model") is None:
        return None
    return spantrace.per_batch_ms(run, lambda s: s.total_s("parse.decode"))
