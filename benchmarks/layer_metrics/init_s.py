"""Seconds inside ``hvd.init()``: the program reaching its devices."""


def read(run):
    return run["spans"].get("init")
