"""ops_per_s: client operations acknowledged inside the window (puts
notified and gets answered) over the window's wall seconds."""


def reduce(rec, trace):
    acked = rec.values.get("acked")
    if acked is None:
        return None
    return acked / rec.window_s
