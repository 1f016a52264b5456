"""Set-up: process start, naming the chip, loading the cell, building the
call stream, the warm-up call and the device probe's compilation."""


def read(w):
    return w.setup_s
