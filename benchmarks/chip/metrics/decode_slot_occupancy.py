"""The share of the parser's decode slot-steps that decoded a live page:
the program's ``parse.live_slot_steps`` over ``parse.slot_steps``
counters across the traced window. The rest are slots whose page had
finished, or that held none, stepping with the longest page."""


def read(run):
    slots = run.counters.get("parse.slot_steps", 0)
    if not slots:
        return None
    return run.counters.get("parse.live_slot_steps", 0) / slots
