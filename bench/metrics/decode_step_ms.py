"""Mean host time of the loop's decode phase (page growth, tables, the
decode program's dispatch, sampling and the token sync), in milliseconds.
None where the program reports no phases."""


def read(name, rec):
    dec = (rec.get("phases") or {}).get("decode")
    if not dec or not dec["n"]:
        return None
    return 1e3 * dec["s"] / dec["n"]
