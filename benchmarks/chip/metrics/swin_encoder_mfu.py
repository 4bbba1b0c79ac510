"""The parser's encoder against the chip's bf16 peak: the FLOPs of the
pages the traced window encoded (``parser_flops.encode_flops`` times
the program's ``parse.pages`` counter: the Swin encoder and the
decoder's cross-attention K/V projections) over the device's busy
seconds inside the program's ``parse.encode`` spans times the peak, in
percent. Padding pages are not counted; device work that another
thread put on the chip inside those spans counts against the share."""
import parser_flops


def read(run):
    spans, pages = getattr(run, "spans", None), run.counters.get(
        "parse.pages", 0)
    spec = run.config.get("parser_model")
    if spans is None or run.peak is None or spec is None or not pages:
        return None
    busy = spans.total_s("parse.encode") - spans.idle_under_s("parse.encode")
    if busy <= 0:
        return None
    return 100.0 * parser_flops.encode_flops(spec) * pages / (
        busy * run.peak["flops_per_s"])
