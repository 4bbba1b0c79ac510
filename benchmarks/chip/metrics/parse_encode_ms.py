"""The parser model's encoder per batch, in milliseconds: the program's
``parse.encode`` spans inside the traced window (pages through the
encoder into the slots' cross-attention K/V, the host waiting for the
device at the end), over the window's batches. A configuration without a parser model has nothing
to read."""
import spantrace


def read(run):
    if run.config.get("parser_model") is None:
        return None
    return spantrace.per_batch_ms(run, lambda s: s.total_s("parse.encode"))
