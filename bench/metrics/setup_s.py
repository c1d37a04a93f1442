"""setup_s: seconds from process start to the window's start (weights,
engine, warm-up and, on a cold cache, compilation)."""


def read(rec):
    return {"value": rec["setup_s"], "unit": "s"}
