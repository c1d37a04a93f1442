"""serve_tokens_per_s: output tokens emitted in the window over the
window's length (host clock; the window ends with the last decode tick
that started in it)."""


def read(rec):
    if rec.get("tokens") is None or rec["window_s"] <= 0:
        return None
    return {"value": rec["tokens"] / rec["window_s"], "unit": "tokens/s",
            "tokens": rec["tokens"], "window_s": rec["window_s"]}
