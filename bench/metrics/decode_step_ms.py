"""Mean device time of one run of the decode program, the model's step
over every slot (``jit_serve_decode`` or ``jit_serve_decode_paged``), in
milliseconds, from the traced window's ``program_s`` (``bench/spans.py``).
None without a device trace, or where no decode program ran in it."""

PROGRAMS = ("jit_serve_decode", "jit_serve_decode_paged")


def read(name, rec):
    spans = rec.get("spans")
    if spans is None:
        return None
    runs = [spans.program_s[p] for p in PROGRAMS if p in spans.program_s]
    n = sum(r["n"] for r in runs)
    return 1e3 * sum(r["s"] for r in runs) / n if n else None
