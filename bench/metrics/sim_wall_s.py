"""Host seconds per ``simulate()`` call: the whole window over the calls it
completed -- what a user waits per sweep point."""


def read(w):
    return w.window_s / len(w.calls) if w.calls else None
