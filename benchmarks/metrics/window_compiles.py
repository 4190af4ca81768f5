"""``executor.metrics.total("xla_compiles")`` over the window: programs
compiled while the clock ran.  Anything but 0 is also said on an
earlier line (``compile_in_window``), with the stage's name."""


def read(trace, spans, counters, cell):
    if "xla_compiles" not in counters:
        return None
    return float(counters["xla_compiles"])
