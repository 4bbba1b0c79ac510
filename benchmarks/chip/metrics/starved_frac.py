"""Share of the traced window in which the device sat idle while the
consumer waited for a prepared batch: the device's idle intervals
inside the program's ``prefetch.wait`` spans, over the window."""


def read(run):
    spans = getattr(run, "spans", None)
    if spans is None or not spans.window_s:
        return None
    return spans.idle_under_s("prefetch.wait") / spans.window_s
