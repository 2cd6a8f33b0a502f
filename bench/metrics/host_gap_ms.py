"""Mean device idle inside one step of the serving loop: over the
``serve.step`` spans that ran device work and lie wholly inside the traced
device window, the device's idle time inside each (``bench/spans.py``), in
milliseconds. None without a device trace or without spans."""


def read(name, rec):
    spans = rec.get("spans")
    return None if spans is None else spans.host_gap_ms
