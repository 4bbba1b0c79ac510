"""The parser's decode against the chip's memory bandwidth: the bytes
that the traced window's greedy steps needed (``parser_flops.
decode_bytes`` from the program's ``parse.decode_steps``,
``parse.live_slot_steps`` and ``parse.live_kv_positions`` counters)
over the device's busy seconds inside the program's ``parse.decode``
spans times the peak bandwidth, in percent. Device work that another
thread put on the chip inside those spans counts against the share."""
import parser_flops


def read(run):
    spans, c = getattr(run, "spans", None), run.counters
    spec = run.config.get("parser_model")
    if spans is None or run.peak is None or spec is None \
            or not c.get("parse.decode_steps"):
        return None
    busy = spans.total_s("parse.decode") - spans.idle_under_s("parse.decode")
    if busy <= 0:
        return None
    moved = parser_flops.decode_bytes(spec, c["parse.decode_steps"],
                                      c.get("parse.live_slot_steps", 0),
                                      c.get("parse.live_kv_positions", 0))
    return 100.0 * moved / (busy * run.peak["hbm_bytes_per_s"])
