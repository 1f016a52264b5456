"""Share of the traced window, in percent, in which no operation ran on the
chip: ``100 * (1 - busy / window)``, busy being the union of the device's
operation intervals (``benchlib/xtrace.py``)."""


def read(w):
    return 100.0 * w.trace["idle_share"] if w.trace else None
