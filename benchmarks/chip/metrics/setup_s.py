"""Process start to the window's open: weights, engine, warm-up (and, in
a run that compiles, compilation), and the fill: a backlog's, or the
arrivals an open-loop mix serves before its window (``fill_s``)."""


def read(rec):
    return rec["window"][0] - rec["process_start"]
